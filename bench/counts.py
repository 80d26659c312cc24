"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's own counts: a roofline share or a utilization is
a count from here divided by a time from the trace.  Model numbers come
from the benchmark's configuration files (``bench/configs``), never from
the program.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Tuple

# ---------------------------------------------------------------------- #
# ResNet (CIFAR/GTSRB form: 3x3 stride-1 stem, basic blocks)
# ---------------------------------------------------------------------- #


def conv_flops(h_out: int, w_out: int, kh: int, kw: int, cin: int,
               cout: int) -> int:
    """Multiply-adds of one NHWC conv on one image, counted as 2 FLOPs."""
    return 2 * h_out * w_out * kh * kw * cin * cout


def resnet_convs(model: dict) -> List[Tuple]:
    """Every conv of one image's forward pass:
    ``(name, h_out, w_out, kh, kw, cin, cout)``."""
    size = model["image_size"]
    convs = [("stem", size, size, 3, 3, model["in_channels"],
              model["widths"][0])]
    in_c, hw = model["widths"][0], size
    for si, (n, w) in enumerate(zip(model["resnet_blocks"],
                                    model["widths"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            out_hw = -(-hw // stride)
            convs.append((f"s{si}b{bi}.conv1", out_hw, out_hw, 3, 3, in_c, w))
            convs.append((f"s{si}b{bi}.conv2", out_hw, out_hw, 3, 3, w, w))
            if stride != 1 or in_c != w:
                convs.append((f"s{si}b{bi}.proj", out_hw, out_hw, 1, 1,
                              in_c, w))
            in_c, hw = w, out_hw
    return convs


def resnet_forward_flops(model: dict) -> int:
    """Forward FLOPs of one image: every conv plus the dense head."""
    conv = sum(conv_flops(*c[1:]) for c in resnet_convs(model))
    head = 2 * model["widths"][-1] * model["num_classes"]
    return conv + head


def resnet_train_flops(model: dict) -> int:
    """FLOPs one image needs for forward and backward: the forward, the
    weight gradient of every layer, and the input gradient of every layer
    but the stem (nothing needs the image's gradient).  Recomputation,
    such as ghost clipping's second pass, is not counted."""
    fwd = resnet_forward_flops(model)
    stem = conv_flops(*resnet_convs(model)[0][1:])
    return 3 * fwd - stem


# ---------------------------------------------------------------------- #
# bytes of an HLO operand list
# ---------------------------------------------------------------------- #
_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s8": 1, "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(r"\b(f64|s64|u64|f32|s32|u32|bf16|f16|s16|u16|"
                       r"f8e4m3fn|f8e5m2|s8|u8|pred)\[([0-9,]*)\]")


def shape_bytes(text: str) -> int:
    """Summed bytes of every array shape written in ``text``, HLO style
    (``f32[256,256]{1,0}``)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def instruction_bytes(text: str) -> int:
    """Bytes an HLO instruction moves: its result and operand shapes, as
    the trace names the op (``%name = <result> op(<operands>), ...``);
    attributes after the operand list, which repeat shapes, are left out."""
    head = text
    for cut in (", custom_call_target=", ", operand_layout_constraints=",
                ", kind=", ", calls=", ", frontend_attributes="):
        head = head.split(cut, 1)[0]
    return shape_bytes(head)


def luq_call_bytes(rows: int, cols: int) -> int:
    """One ``luq_quant`` kernel call over a (rows, cols) f32 view: read x
    and its uniforms, write the codes back as f32, read the scale."""
    return 3 * 4 * rows * cols + 4


# ---------------------------------------------------------------------- #
# dense decoder
# ---------------------------------------------------------------------- #
def transformer_matmul_params(model: dict) -> int:
    """Weights a token multiplies through: the block projections and the
    output head (the embedding gather is not a matmul)."""
    d, h, kv, hd, f = (model["d_model"], model["n_heads"],
                       model["n_kv_heads"], model["head_dim"], model["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def token_flops(model: dict, live: int) -> int:
    """FLOPs of one token that attends over ``live`` positions (its own
    included): 2 per weight, and QK plus PV per layer."""
    attn = 4 * live * model["n_heads"] * model["head_dim"]
    return (2 * transformer_matmul_params(model)
            + model["n_layers"] * attn)


def prefill_flops(model: dict, prompt_len: int) -> int:
    """FLOPs of a causal prefill of ``prompt_len`` tokens (unpadded)."""
    p = prompt_len
    attn_live = p * (p + 1) // 2
    return (2 * transformer_matmul_params(model) * p
            + model["n_layers"] * 4 * attn_live * model["n_heads"]
            * model["head_dim"])


def decode_attn_bytes(live_lengths: Iterable[int], model: dict,
                      code_bytes: int = 1, scale_bytes: int = 2,
                      act_bytes: int = 2) -> int:
    """Bytes one decode tick's attention needs over all layers: for each
    active slot the K and V codes and scales of its live positions, its
    query row in and its context row out."""
    L, kv, h, hd = (model["n_layers"], model["n_kv_heads"],
                    model["n_heads"], model["head_dim"])
    total = 0
    for live in live_lengths:
        total += 2 * kv * live * (hd * code_bytes + scale_bytes)
        total += 2 * h * hd * act_bytes
    return L * total
