"""Trip-count-aware HLO analyzer vs analytic FLOP counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze
from repro.launch import roofline


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out.sum()

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze(_compile(f, sds, sds).as_text())
    expected = 10 * 2 * 128 ** 3
    assert abs(r["flops"] - expected) / expected < 0.05
    assert not r["warnings"]


def test_nested_scan_flops():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out.sum()

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze(_compile(f, sds, sds).as_text())
    expected = 12 * 2 * 128 ** 3
    assert abs(r["flops"] - expected) / expected < 0.05


def test_plain_matmul_exact():
    f = lambda a, b: a @ b
    r = analyze(_compile(
        f, jax.ShapeDtypeStruct((256, 512), jnp.float32),
        jax.ShapeDtypeStruct((512, 128), jnp.float32)).as_text())
    assert r["flops"] == 2 * 256 * 512 * 128


def test_conv_flops_exact():
    def f(x, w):
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
        return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                            dimension_numbers=dn)
    r = analyze(_compile(
        f, jax.ShapeDtypeStruct((2, 16, 16, 8), jnp.float32),
        jax.ShapeDtypeStruct((3, 3, 8, 4), jnp.float32)).as_text())
    assert r["flops"] == 2 * 2 * 16 * 16 * 4 * 3 * 3 * 8


def test_bytes_scale_with_loop():
    def body_once(x):
        return jnp.tanh(x * 2.0)

    def looped(x):
        def body(c, _):
            return jnp.tanh(c * 2.0), None
        out, _ = jax.lax.scan(body, x, None, length=50)
        return out

    sds = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    b1 = analyze(_compile(body_once, sds).as_text())["bytes"]
    b50 = analyze(_compile(looped, sds).as_text())["bytes"]
    assert b50 > 20 * b1


def test_roofline_terms():
    terms = roofline.derive({}, "", "TPU v5 lite", hlo_analysis={
        "flops": 197e12, "bytes": 819e9, "collectives": {"all-reduce": 25e9},
        "collective_bytes": 25e9, "collective_wire_bytes": 50e9,
        "warnings": [], "entry": "main"})
    assert abs(terms.compute_s - 1.0) < 1e-9
    assert abs(terms.memory_s - 1.0) < 1e-9
    assert abs(terms.collective_s - 1.0) < 1e-9


def test_roofline_rejects_unlisted_device():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.chip_peaks("cpu")


def test_model_flops_moe_active():
    from repro.configs import get_smoke_config
    from repro.models.registry import build_model
    from repro.config import QuantConfig
    cfg = get_smoke_config("kimi-k2-1t-a32b")
    model = build_model(cfg, QuantConfig())
    ap = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    total = roofline.count_params(ap)
    active = roofline.active_params(cfg, ap)
    assert active < total
