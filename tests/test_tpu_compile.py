"""Every Pallas kernel compiles for a TPU v5e chip at the widths it runs at.

Interpret mode cannot see the TPU lowering rules (block tiling, VMEM
stores, memory-space placement), so these tests lower the public wrappers
of ``repro.kernels.ops`` with ``interpret=False`` against a *described*
``v5e:2x2`` topology and compile them with the TPU compiler, which is
installed without a chip.  Nothing runs.  The widths are the ones the main
paths produce: the ResNet18 conv operands of a DP training step (batch
256, microbatch 16) and decode attention at stablelm-3b's 32 KV heads over
a 2048-token cache.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

KEY = (2,), jnp.uint32


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    # the kernel is really there: interpret mode would leave plain XLA ops
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _resnet18_param_count() -> int:
    from repro.config import QuantConfig
    from repro.configs import get_config
    from repro.models.registry import build_model
    model = build_model(get_config("resnet18"), QuantConfig())
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))


# conv weights (HWIO) and per-example activations (NHWC) of ResNet18 at 32x32
@pytest.mark.parametrize("shape", [(3, 3, 512, 512), (16, 32, 32, 64),
                                   (16, 4, 4, 512)])
def test_luq_quantize_compiles(chip, shape):
    _compile(ops.luq_quantize, _sds(chip, shape), _sds(chip, *KEY))


# im2col GEMMs of the first and last ResNet18 stages (microbatch 16)
@pytest.mark.parametrize("mkn", [(16384, 576, 64), (256, 4608, 512)])
def test_luq_matmul_compiles(chip, mkn):
    m, k, n = mkn
    _compile(ops.luq_matmul, _sds(chip, (m, k)), _sds(chip, (k, n)),
             _sds(chip, *KEY))


def test_clip_and_sum_compiles(chip):
    # one microbatch of flattened per-example ResNet18 gradients
    d = _resnet18_param_count()
    _compile(ops.clip_and_sum, _sds(chip, (16, d)), clip_norm=1.0)


# ghost taps: (T, Din) im2col patches and (T, Dout) cotangents per example,
# at the stages where T <= GHOST_NORM_MAX_T (16x16, 8x8, 4x4 outputs)
@pytest.mark.parametrize("t,din,dout", [(256, 1152, 128), (64, 2304, 256),
                                        (16, 4608, 512)])
def test_ghost_norm_compiles(chip, t, din, dout):
    _compile(ops.ghost_norm_sq, _sds(chip, (t, din)), _sds(chip, (t, dout)),
             _sds(chip, *KEY), _sds(chip, *KEY))


STABLELM_KV, STABLELM_HD, MAX_SEQ, SLOTS = 32, 80, 2048, 8


@pytest.mark.parametrize("fmt", ["int8", "luq_fp4"])
def test_kv_quant_rows_compiles(chip, fmt):
    # one prefill's K rows: (slot, seq, kv-head, head_dim)
    _compile(ops.kv_quant_rows,
             _sds(chip, (1, MAX_SEQ, STABLELM_KV, STABLELM_HD)), fmt=fmt)


@pytest.mark.parametrize("fmt", ["int8", "luq_fp4"])
def test_decode_attn_compiles(chip, fmt):
    code_dim = STABLELM_HD if fmt == "int8" else STABLELM_HD // 2
    code_dt = jnp.int8 if fmt == "int8" else jnp.uint8
    cache = (SLOTS, STABLELM_KV, MAX_SEQ)
    _compile(ops.decode_attn_fused,
             _sds(chip, (SLOTS, STABLELM_KV, STABLELM_HD)),
             _sds(chip, cache + (code_dim,), code_dt),
             _sds(chip, cache + (code_dim,), code_dt),
             _sds(chip, cache, jnp.bfloat16), _sds(chip, cache, jnp.bfloat16),
             _sds(chip, (SLOTS,), jnp.int32),
             fmt=fmt, n_kv=STABLELM_KV, scale=STABLELM_HD ** -0.5)


def test_decode_slots_updates_the_donated_cache_in_place(chip, monkeypatch):
    """The fused decode tick over a donated int8 slot cache, at stablelm-3b's
    widths with two layers and 14 slots x 2048, holds no second stacked
    cache: the layer scan carries the cache and writes only the new rows,
    so the step's temporaries stay below one stacked cache's bytes."""
    import dataclasses

    from repro.config import QuantConfig
    from repro.configs import get_config
    from repro.models.registry import build_model
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    cfg = dataclasses.replace(get_config("stablelm-3b"), n_layers=2,
                              param_dtype="bfloat16")
    model = build_model(cfg, QuantConfig(fmt="none", backend="pallas"))
    slots = 14

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = sds(model.slot_cache_spec(slots, MAX_SEQ, kv_fmt="int8"))

    def step(p, c, tokens, active):
        logits, c = model.decode_slots(p, c, tokens, active, kv_fmt="int8")
        return jnp.argmax(logits, -1), c

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, _sds(chip, (slots,), jnp.int32),
        _sds(chip, (slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    stacked = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for name, a in cache.items() if name != "pos")
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= stacked
    assert ma.temp_size_in_bytes < stacked, (ma.temp_size_in_bytes, stacked)
