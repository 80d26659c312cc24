"""Hand counts for the FLOP and byte functions of bench/counts.py."""
import json
from pathlib import Path

from bench import counts

CFG = Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CFG / f"{name}.json").read_text())["model"]


def test_resnet18_stage1_conv():
    # 3x3 conv, 64 -> 64 channels, 32x32 output: 2 * 32*32 * 9 * 64 * 64
    assert counts.conv_flops(32, 32, 3, 3, 64, 64) == 75_497_472
    convs = {c[0]: c[1:] for c in counts.resnet_convs(_model("resnet18"))}
    assert convs["s0b0.conv1"] == (32, 32, 3, 3, 64, 64)
    assert convs["s1b0.conv1"] == (16, 16, 3, 3, 64, 128)
    assert convs["s1b0.proj"] == (16, 16, 1, 1, 64, 128)
    assert convs["s3b1.conv2"] == (4, 4, 3, 3, 512, 512)
    assert "s0b0.proj" not in convs and len(convs) == 20


def test_resnet18_train_flops():
    m = _model("resnet18")
    fwd = counts.resnet_forward_flops(m)
    # stem 2*32*32*27*64 + stages; head 2*512*43
    stem = 2 * 32 * 32 * 27 * 64
    assert fwd == sum(counts.conv_flops(*c[1:])
                      for c in counts.resnet_convs(m)) + 2 * 512 * 43
    assert counts.resnet_train_flops(m) == 3 * fwd - stem
    # by stage, written out: stage 0 four 3x3 64->64 convs at 32x32; each
    # later stage a strided 3x3, three 3x3 and a 1x1 projection at half
    # the side and twice the width
    st = [4 * 2 * 32 * 32 * 9 * 64 * 64]
    for hw, ci, co in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        st.append(2 * hw * hw * (9 * ci * co + 3 * 9 * co * co + ci * co))
    assert fwd == stem + sum(st) + 2 * 512 * 43 == 1_110_879_232


def test_luq_call_bytes():
    # a (9216, 256) f32 view: x and uniforms in, codes out, one scale
    assert counts.luq_call_bytes(9216, 256) == 3 * 4 * 9216 * 256 + 4
    # the op as a v5e trace names it (bench/tests/data/v5e_probe.xplane.pb)
    text = ("%luq_quantize.1 = f32[2048,256]{1,0:T(8,128)S(1)} custom-call("
            "f32[2048,256]{1,0:T(8,128)S(1)} %reshape.1, f32[2048,256]{1,0:"
            "T(8,128)S(1)} %add_maximum_fusion, f32[1,1]{1,0:T(1,128)} "
            "%bitcast.2), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={f32[2048,256]{1,0}, f32[2048,256]"
            "{1,0}, f32[1,1]{1,0}}, frontend_attributes={kernel_metadata={}}")
    assert counts.instruction_bytes(text) == counts.luq_call_bytes(2048, 256)


def test_decode_attn_tick_bytes():
    m = _model("stablelm-3b")
    # two active slots at live lengths 100 and 300: per layer, K and V
    # codes (80 B) plus a 2 B scale for each live row and kv head, and a
    # bf16 query row in and context row out for each slot
    per_layer = 2 * 32 * (100 + 300) * (80 + 2) + 2 * (2 * 32 * 80 * 2)
    assert counts.decode_attn_bytes([100, 300], m) == 32 * per_layer


def test_transformer_token_flops():
    m = _model("stablelm-3b")
    per_layer = 2560 * 32 * 80 * 3 + 32 * 80 * 2560 + 3 * 2560 * 6912
    params = 32 * per_layer + 2560 * 50304
    assert counts.transformer_matmul_params(m) == params
    assert counts.token_flops(m, 10) == 2 * params + 32 * 4 * 10 * 32 * 80
    assert counts.prefill_flops(m, 3) == sum(counts.token_flops(m, k)
                                             for k in (1, 2, 3))


def test_decode_mfu_reader():
    from bench import harness
    m = _model("stablelm-3b")
    reader = harness.load_module(harness.BENCH / "metrics" / "decode_mfu.py")
    # two ticks of 0.1 s in the window, one after it; request 0 has its
    # prefill token and two decoded tokens in the window, request 1 one
    record = {"peaks": {"bf16_flops": 1e15},
              "serve": {"window_s": 1.0, "model": m,
                        "ticks": [(0.3, 0.1), (0.5, 0.1), (1.2, 0.1)],
                        "requests": [
                            {"prompt_len": 10, "stamps": [0.1, 0.3, 0.5, 1.2]},
                            {"prompt_len": 4, "stamps": [0.4, 0.5]}]}}
    flops = (counts.token_flops(m, 11) + counts.token_flops(m, 12)
             + counts.token_flops(m, 5))
    assert reader.read(record) == 100.0 * flops / 0.2 / 1e15
