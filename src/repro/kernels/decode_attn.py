"""Pallas TPU kernels: KV-row quantization + fused decode attention.

Two kernels back the ``kv_quant`` / ``decode_attn`` ops of the quantizer
dispatch (``repro.quant.backend``) for the quantized cache formats
(``int8`` / ``luq_fp4``):

``kv_rowquant_2d``   one VMEM pass per row block: per-row amax, the
                     bf16-rounded scale, and the integer codes — the row
                     never round-trips HBM between scale computation and
                     encoding (the unfused path reads it twice).

``decode_attn_call`` one VMEM pass per (slot, kv-head) grid step: load the
                     packed code rows + their scales, decode (int8 cast /
                     fp4 low and high nibbles as two planes) in
                     registers, fold the K scales
                     into the post-QK scores and the V scales into the
                     pre-PV probabilities, mask by the slot's position,
                     softmax, PV — the dequantized cache never exists in
                     HBM and the scale multiplies land on the small
                     (g, S) score matrix instead of the (S, hd) operands.

Elementwise encode/decode math is imported from ``repro.quant.kv_cache``
— the same expressions the ref backend evaluates — so ref-vs-pallas
parity is a layout question, not a numerics question.  Wrappers that own
padding / packing / interpret-mode live in ``repro.kernels.ops``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.quant.kv_cache import (fp4_decode_unit, fp4_encode, fp4_row_scale,
                                  int8_encode, int8_row_scale)


# --------------------------------------------------------------------------- #
# KV-row quantization
# --------------------------------------------------------------------------- #
def _kv_rowquant_kernel(fmt, x_ref, codes_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                    # (br, D)
    amax = jnp.max(jnp.abs(x), axis=-1)                   # (br,)
    if fmt == "int8":
        scale = int8_row_scale(amax)
        codes = int8_encode(x, scale).astype(jnp.int8)
    else:  # luq_fp4 — unpacked codes 0..15; the wrapper packs nibbles
        scale = fp4_row_scale(amax)
        codes = fp4_encode(x, scale).astype(jnp.int8)
    codes_ref[...] = codes
    scale_ref[...] = scale[:, None]


def kv_rowquant_2d(x: jax.Array, fmt: str, block_rows: int = 128,
                   interpret: bool = False):
    """``x``: (R, D) f32 rows, R a ``block_rows`` multiple, D lane-padded
    by the wrapper (zero columns never set the row amax of a nonzero row,
    and all-zero rows get scale 0 -> zero codes).  Returns ``(codes,
    scales)``: (R, D) int8 codes (luq_fp4: values 0..15, one per element —
    packing is the wrapper's job) and (R, 1) f32 scales (exact bf16
    values, cast to bf16 by the wrapper)."""
    r, d = x.shape
    assert r % block_rows == 0, (x.shape, block_rows)
    kernel = lambda *refs: _kv_rowquant_kernel(fmt, *refs)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, d), jnp.int8),
                   jax.ShapeDtypeStruct((r, 1), jnp.float32)],
        interpret=interpret,
        name="kv_quant",
    )(x)


# --------------------------------------------------------------------------- #
# fused decode attention over the quantized slot pool
# --------------------------------------------------------------------------- #
def _value_planes(fmt, codes):
    """Stored code block (S, Dp) -> unscaled f32 value planes, each (S, Dp).

    int8 has one plane.  luq_fp4 has two: the low nibbles (even head_dim
    indices) and the high nibbles (odd ones).  Keeping them apart means the
    kernel never interleaves lanes; the wrapper splits q and joins the
    context along the same even/odd layout.
    """
    if fmt == "int8":
        return (codes.astype(jnp.float32),)
    c = codes.astype(jnp.int32)
    return fp4_decode_unit(c & 0xF), fp4_decode_unit((c >> 4) & 0xF)


def _decode_attn_kernel(fmt, scale, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                        pos_ref, o_ref):
    kplanes = _value_planes(fmt, kc_ref[0, 0])            # P x (S, Dp)
    vplanes = _value_planes(fmt, vc_ref[0, 0])
    ks = ks_ref[0, 0]                                     # (1, S)
    vs = vs_ref[0, 0]
    # QK with the K scales folded into the (g, S) score matrix
    scores = sum(
        jax.lax.dot_general(q_ref[0, 0, p].astype(jnp.float32), kp,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        for p, kp in enumerate(kplanes))
    scores = scores * (ks * scale)
    valid = (jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
             <= pos_ref[pl.program_id(0)])
    scores = jnp.where(valid, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    # PV with the V scales folded into the probabilities (probs * vs is
    # (g, S) — far cheaper than scaling the (S, hd) value rows)
    w = probs * vs
    for p, vp in enumerate(vplanes):
        o_ref[0, 0, p] = jax.lax.dot_general(
            w, vp, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def decode_attn_call(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                     v_codes: jax.Array, v_scale: jax.Array, pos: jax.Array,
                     fmt: str, scale: float, interpret: bool = False):
    """Fused decode attention over a quantized cache, one grid step per
    (slot, kv-head).

    ``q``: (B, KV, P, g, Dp) f32, split into the P value planes of
    ``_value_planes`` (int8: P = 1; luq_fp4: P = 2, even then odd head_dim
    indices), g and Dp tile-padded; ``k_codes`` / ``v_codes``:
    (B, KV, S, Dp) stored rows (int8: Dp = hd; luq_fp4: Dp = hd // 2);
    ``k_scale``/``v_scale``: (B, KV, 1, S) f32 (a row per (slot,
    kv-head), so each block's last two dims are the array's own);
    ``pos``: (B,) int32 per-slot positions, read from SMEM.  Padded S rows
    carry zero scales and indices beyond every ``pos``, so they contribute
    exactly zero.  Returns (B, KV, P, g, Dp) f32 context rows
    in the same plane layout as ``q``.
    """
    b, kv, planes, g, dp = q.shape
    s = k_codes.shape[2]
    kernel = lambda *refs: _decode_attn_kernel(fmt, scale, *refs)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(b, kv),
        in_specs=[
            pl.BlockSpec((1, 1, planes, g, dp),
                         lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, 1, s, dp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, s), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, s, dp), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, s), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, planes, g, dp),
                               lambda i, j: (i, j, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, planes, g, dp), jnp.float32),
        interpret=interpret,
        name="decode_attn",
    )(q, k_codes, k_scale, v_codes, v_scale, pos)
