"""Pallas TPU kernel: LUQ-FP4 stochastic quantizer (elementwise).

Grid tiles the (padded) 2-D view of the tensor into VMEM blocks; random bits
are an explicit input (threefry generated in-graph) so the kernel is
deterministic given the key — required for DP auditing and SPMD consistency.
The per-tensor scale alpha = max|x| is computed outside (one pass) and passed
as a (1, 1) scalar block broadcast to every tile; fusing the max would make
the kernel two-pass for no HBM saving (x is read once either way).

Block shape default (256, 256) = 256 KiB fp32 in + 256 KiB rand + 256 KiB out
per tile -> well under VMEM; lanes dim is a 128-multiple for clean VREG
layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.formats import LUQ_EXP_LEVELS


def luq_stochastic_round(x, u, alpha):
    """The LUQ-FP4 elementwise math (f32 in/out), shared by the quantize
    and fused ghost-norm kernels so their bits cannot drift apart.
    Mirrors ``repro.quant.formats.luq_fp4`` exactly."""
    safe_alpha = jnp.where(alpha > 0, alpha, 1.0)
    sign = jnp.sign(x)
    y = jnp.abs(x) / safe_alpha
    min_level = 2.0 ** (-(LUQ_EXP_LEVELS - 1))
    p_under = y / min_level
    under = jnp.where(u < p_under, min_level, 0.0)
    ylog = jnp.log2(jnp.maximum(y, min_level))
    k = jnp.clip(jnp.floor(ylog), -(LUQ_EXP_LEVELS - 1), 0.0)
    low = jnp.exp2(k)
    high = jnp.minimum(jnp.exp2(k + 1.0), 1.0)
    p_up = (y - low) / jnp.maximum(high - low, 1e-30)
    rounded = jnp.where(u < p_up, high, low)
    q = jnp.where(y < min_level, under, rounded)
    return jnp.where(alpha > 0, sign * q * safe_alpha, 0.0)


def _luq_kernel(x_ref, u_ref, alpha_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    out = luq_stochastic_round(x, u_ref[...], alpha_ref[0, 0])
    o_ref[...] = out.astype(o_ref.dtype)


def luq_quant_2d(x: jax.Array, u: jax.Array, alpha: jax.Array,
                 block=(256, 256), interpret: bool = False) -> jax.Array:
    """x, u: (M, N) with M % block[0] == N % block[1] == 0; alpha: scalar."""
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    alpha2d = alpha.reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        _luq_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="luq_quant",
    )(x, u, alpha2d)
