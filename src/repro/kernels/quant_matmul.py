"""Pallas TPU kernel: fused LUQ-FP4 quantize-both-operands matmul.

The TPU-native adaptation of the paper's FP4 GEMM (DESIGN.md §3): instead of
a separate fake-quant pass + GEMM (two HBM round trips on GPU), each (bm, bk)
A-tile and (bk, bn) B-tile is quantized *in VMEM* right before feeding the
MXU, accumulating fp32 in a VMEM scratch across the k grid dimension.
Quantization therefore adds zero HBM traffic; on FP4 hardware the dequant
multiply folds into the MXU pipeline.

Tile defaults (128, 128, 512): A-tile 256 KiB + B-tile 256 KiB + acc 64 KiB
(+ random tiles) fits VMEM with double buffering; all dims are 128-multiples
(MXU-aligned).

Random bits: two uniform tensors, tiled like A and B.  Per-tensor scales are
precomputed (single fused max pass) and passed as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.quant.formats import LUQ_EXP_LEVELS


def _luq(x, u, alpha):
    safe_alpha = jnp.where(alpha > 0, alpha, 1.0)
    sign = jnp.sign(x)
    y = jnp.abs(x) / safe_alpha
    min_level = 2.0 ** (-(LUQ_EXP_LEVELS - 1))
    under = jnp.where(u < y / min_level, min_level, 0.0)
    ylog = jnp.log2(jnp.maximum(y, min_level))
    k = jnp.clip(jnp.floor(ylog), -(LUQ_EXP_LEVELS - 1), 0.0)
    low = jnp.exp2(k)
    high = jnp.minimum(jnp.exp2(k + 1.0), 1.0)
    rounded = jnp.where(u < (y - low) / jnp.maximum(high - low, 1e-30),
                        high, low)
    q = jnp.where(y < min_level, under, rounded)
    return jnp.where(alpha > 0, sign * q * safe_alpha, 0.0)


def _qmm_kernel(a_ref, b_ref, ua_ref, ub_ref, aa_ref, ab_ref, o_ref,
                acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    aq = _luq(a, ua_ref[...], aa_ref[0, 0])
    bq = _luq(b, ub_ref[...], ab_ref[0, 0])
    acc_ref[...] += jnp.dot(aq, bq, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul(a: jax.Array, b: jax.Array, ua: jax.Array, ub: jax.Array,
                 alpha_a: jax.Array, alpha_b: jax.Array,
                 block=(128, 128, 512), interpret: bool = False) -> jax.Array:
    """(M, K) x (K, N) with in-tile LUQ quantization of both operands."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, block)
    k_steps = k // bk
    aa = alpha_a.reshape(1, 1).astype(jnp.float32)
    ab = alpha_b.reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="quant_matmul",
    )(a, b, ua, ub, aa, ab)
