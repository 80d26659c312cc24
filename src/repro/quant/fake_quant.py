"""Fake-quantized GEMM/conv primitives (paper A.12, Fig. 7).

The paper's simulation quantizes the inputs of all three GEMMs of a layer:

    forward :  y  = Q(x)  . Q(w)
    dgrad   :  dx = Q(g)  . Q(w)^T
    wgrad   :  dw = Q(x)^T . Q(g)

We implement this once, generically, with ``jax.custom_vjp``: the backward
GEMMs are derived mechanically from the forward contraction via
``jax.linear_transpose``, so the same primitive serves einsums of any
rank (dense, QKV projections, MoE expert matmuls) and convolutions.

Policy flags are *traced* scalars: ``flag`` in {0., 1.} selects the quantized
or the full-precision path via ``lax.cond`` — switching the DPQuant policy
never triggers recompilation (flags are just inputs).

Randomness: stochastic formats consume explicit uint32 seeds; each GEMM input
gets an independent fold so forward/dgrad/wgrad re-quantizations are
independent draws, as in LUQ.  ``seed`` and ``fold`` are folded into the key
*separately* — a combined ``seed + fold`` would make (seed=s, fold=1) collide
with (seed=s+1, fold=0), correlating draws across adjacent steps/GEMMs.

``backend`` selects the quantizer implementation through
``repro.quant.backend`` ("ref" jnp formats or the "pallas" fused kernels);
the ``REPRO_QUANT_BACKEND`` env var overrides it globally.

Ghost-clipping integration (``repro.dp.ghost``): when a ghost context is
active at trace time, ``qeinsum``/``qconv2d`` route to the ghost-tapped
custom-VJP variants (norm pass) or enable per-example quantization
semantics on the batched activation/cotangent operands (grad pass) — see
the module docstring of ``repro.dp.ghost`` for the parity argument.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.quant import backend as qbackend
from repro.runtime.tracing import scope


def _maybe_quant(x, seed: jax.Array, fold: int, fmt: str, flag: jax.Array,
                 backend: str = "ref", per_example: bool = False):
    """Quantize ``x`` when ``flag > 0.5``, else pass through. ``seed`` uint32.

    ``per_example=True`` (ghost grad pass, batched operands only) applies
    the quantizer to each (1, ...) example slice with the shared key —
    per-example max scaling and hoisted draws, bit-matching the vmap DP
    path's per-lane quantization (repro.dp.ghost.per_example_quantizer).
    """
    if fmt == "none":
        return x
    q, _ = qbackend.get_quantizer(fmt, backend)
    if per_example:
        from repro.dp.ghost import per_example_quantizer
        q = per_example_quantizer(q)

    def do_q(v):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed), fold)
        return q(v, key)

    return jax.lax.cond(flag > 0.5, scope("quantize", do_q), lambda v: v, x)


@functools.lru_cache(maxsize=None)
def _make_qeinsum(spec: str, fmt: str, q_fwd: bool, q_dgrad: bool,
                  q_wgrad: bool, backend: str, per_example: bool = False):
    """Build a custom-VJP einsum with quantized fwd/dgrad/wgrad GEMM inputs.

    ``per_example`` switches the *batched* operands (activation ``x`` and
    cotangent ``g`` — never the weight) to per-example quantization for
    the ghost grad pass.
    """

    def einsum(x, w):
        return jnp.einsum(spec, x, w)

    @jax.custom_vjp
    def qeinsum(x, w, seed, flag):
        xq = (_maybe_quant(x, seed, 0, fmt, flag, backend, per_example)
              if q_fwd else x)
        wq = _maybe_quant(w, seed, 1, fmt, flag, backend) if q_fwd else w
        return einsum(xq, wq)

    def fwd(x, w, seed, flag):
        return qeinsum(x, w, seed, flag), (x, w, seed, flag)

    def bwd(res, g):
        x, w, seed, flag = res
        # dgrad: dx = GEMM(Q(g), Q(w)) via the transpose of y = einsum(x, w).
        wq = _maybe_quant(w, seed, 2, fmt, flag, backend) if q_dgrad else w
        gq_d = (_maybe_quant(g, seed, 3, fmt, flag, backend, per_example)
                if q_dgrad else g)
        dx_fn = jax.linear_transpose(lambda t: einsum(t, wq), x)
        (dx,) = dx_fn(gq_d)
        # wgrad: dw = GEMM(Q(x), Q(g)).
        xq = (_maybe_quant(x, seed, 4, fmt, flag, backend, per_example)
              if q_wgrad else x)
        gq_w = (_maybe_quant(g, seed, 5, fmt, flag, backend, per_example)
                if q_wgrad else g)
        dw_fn = jax.linear_transpose(lambda t: einsum(xq, t), w)
        (dw,) = dw_fn(gq_w)
        return dx, dw, None, None

    qeinsum.defvjp(fwd, bwd)
    return qeinsum


def qeinsum(spec: str, x: jax.Array, w: jax.Array, *, seed: jax.Array,
            flag: jax.Array, fmt: str = "luq_fp4",
            q_fwd: bool = True, q_dgrad: bool = True, q_wgrad: bool = True,
            backend: str = None):
    """Quantization-aware einsum. ``flag`` and ``seed`` are traced scalars."""
    # Resolve env override *before* the lru_cache key so flipping
    # REPRO_QUANT_BACKEND mid-process cannot serve a stale closure.
    backend = qbackend.resolve_backend(backend)
    seed = jnp.asarray(seed, jnp.uint32)
    flag = jnp.asarray(flag, jnp.float32)
    from repro.dp import ghost
    ctx = ghost.current()
    if ctx is not None and ctx.mode == "norm":
        fn = ghost.make_ghost_qeinsum(spec, fmt, q_fwd, q_dgrad, q_wgrad,
                                      backend)
        return fn(x, w, seed, flag, ctx.tap)
    per_example = ctx is not None and ctx.mode == "grad"
    fn = _make_qeinsum(spec, fmt, q_fwd, q_dgrad, q_wgrad, backend,
                       per_example)
    return fn(x, w, seed, flag)


@functools.lru_cache(maxsize=None)
def _make_qconv(fmt: str, q_fwd: bool, q_dgrad: bool, q_wgrad: bool,
                strides: tuple, padding: str, dnums_key: tuple, backend: str,
                per_example: bool = False, rhs_dilation: tuple = (1, 1),
                feature_groups: int = 1):
    dn = jax.lax.ConvDimensionNumbers(*dnums_key)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, strides, padding, rhs_dilation=rhs_dilation,
            dimension_numbers=dn, feature_group_count=feature_groups)

    @jax.custom_vjp
    def qconv(x, w, seed, flag):
        xq = (_maybe_quant(x, seed, 0, fmt, flag, backend, per_example)
              if q_fwd else x)
        wq = _maybe_quant(w, seed, 1, fmt, flag, backend) if q_fwd else w
        return conv(xq, wq)

    def fwd(x, w, seed, flag):
        return qconv(x, w, seed, flag), (x, w, seed, flag)

    def bwd(res, g):
        x, w, seed, flag = res
        wq = _maybe_quant(w, seed, 2, fmt, flag, backend) if q_dgrad else w
        gq_d = (_maybe_quant(g, seed, 3, fmt, flag, backend, per_example)
                if q_dgrad else g)
        dx_fn = jax.linear_transpose(lambda t: conv(t, wq), x)
        (dx,) = dx_fn(gq_d)
        xq = (_maybe_quant(x, seed, 4, fmt, flag, backend, per_example)
              if q_wgrad else x)
        gq_w = (_maybe_quant(g, seed, 5, fmt, flag, backend, per_example)
                if q_wgrad else g)
        dw_fn = jax.linear_transpose(lambda t: conv(xq, t), w)
        (dw,) = dw_fn(gq_w)
        return dx, dw, None, None

    qconv.defvjp(fwd, bwd)
    return qconv


def qconv2d(x: jax.Array, w: jax.Array, *, seed: jax.Array, flag: jax.Array,
            strides=(1, 1), padding="SAME", fmt: str = "luq_fp4",
            q_fwd: bool = True, q_dgrad: bool = True, q_wgrad: bool = True,
            backend: str = None, rhs_dilation=(1, 1), feature_groups: int = 1):
    """Quantization-aware NHWC conv2d (weights HWIO).

    ``rhs_dilation``/``feature_groups`` map to the same-named
    ``lax.conv_general_dilated`` knobs; under ghost norm passes those
    layers use the per-layer direct-norm fallback (the patches unfold
    identity only covers dense undilated convs — see repro.dp.ghost).
    """
    backend = qbackend.resolve_backend(backend)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    seed = jnp.asarray(seed, jnp.uint32)
    flag = jnp.asarray(flag, jnp.float32)
    from repro.dp import ghost
    ctx = ghost.current()
    if ctx is not None and ctx.mode == "norm":
        fn = ghost.make_ghost_qconv(fmt, q_fwd, q_dgrad, q_wgrad,
                                    tuple(strides), padding, tuple(dn),
                                    tuple(w.shape[:2]), backend,
                                    tuple(rhs_dilation), feature_groups)
        return fn(x, w, seed, flag, ctx.tap)
    per_example = ctx is not None and ctx.mode == "grad"
    fn = _make_qconv(fmt, q_fwd, q_dgrad, q_wgrad, tuple(strides), padding,
                     tuple(dn), backend, per_example, tuple(rhs_dilation),
                     feature_groups)
    return fn(x, w, seed, flag)
