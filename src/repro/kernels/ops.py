"""Jit'd public wrappers for the Pallas kernels.

These own the plumbing the raw kernels don't: uniform-bit generation from a
PRNG key, per-tensor scale computation, padding to tile multiples, and
interpret-mode selection: kernels are compiled when JAX's default backend
is a TPU and run in interpret mode everywhere else (tests that want either
pass ``interpret`` explicitly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attn import decode_attn_call, kv_rowquant_2d
from repro.kernels.ghost_norm import ghost_norm_gram
from repro.kernels.luq_quant import luq_quant_2d
from repro.kernels.per_sample_clip import per_sample_clip
from repro.kernels.quant_matmul import quant_matmul
from repro.quant import kv_cache as kvc


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult0, mult1):
    m, n = x.shape
    pm = (-m) % mult0
    pn = (-n) % mult1
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, (m, n)


def _luq_draw_shape(n: int, block=(256, 256)):
    """The padded 2-d view ``luq_quantize`` draws its uniforms over, for a
    tensor of ``n`` elements.  Threefry pairs the first and second halves
    of the counter array, so ``uniform(key, N)[:n] != uniform(key, (n,))``
    — the draw for element i depends on the TOTAL element count, making
    this shape part of the bit-parity contract.  Single source of truth:
    both ``luq_quantize`` and ``luq_uniform`` derive their draws from it,
    so they cannot drift apart."""
    cols = 256
    rows = -(-n // cols)
    rows += (-rows) % block[0]
    cols += (-cols) % block[1]
    return rows, cols


def luq_uniform(key, shape, block=(256, 256)) -> jax.Array:
    """The uniform draws ``luq_quantize`` consumes for a tensor of
    ``shape``, reshaped back to ``shape`` — what a fused kernel
    (``ghost_norm_sq``) uses to be bit-identical to the quantize kernel
    for the same ``(tensor, key)``."""
    n = int(np.prod(shape))
    u = jax.random.uniform(key, _luq_draw_shape(n, block), jnp.float32)
    return u.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def luq_quantize(x: jax.Array, key: jax.Array, block=(256, 256),
                 interpret=None) -> jax.Array:
    """LUQ-FP4 stochastic quantization of an arbitrary-shape tensor."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    flat = x.reshape(-1)
    # view as 2d, lanes-aligned
    n = flat.shape[0]
    cols = 256
    rows = -(-n // cols)
    flat = jnp.pad(flat, (0, rows * cols - n))
    x2 = flat.reshape(rows, cols)
    x2, _ = _pad_to(x2, block[0], block[1])
    assert x2.shape == _luq_draw_shape(n, block), (x2.shape, n)
    u = jax.random.uniform(key, x2.shape, jnp.float32)
    alpha = jnp.max(jnp.abs(x.astype(jnp.float32)))
    q = luq_quant_2d(x2, u, alpha, block=block, interpret=interpret)
    return q.reshape(-1)[:n].reshape(shape).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def luq_matmul(a: jax.Array, b: jax.Array, key: jax.Array,
               block=(128, 128, 512), interpret=None) -> jax.Array:
    """Fused LUQ-quantize-both-operands matmul: (M,K) @ (K,N) -> f32."""
    interpret = _interpret_default() if interpret is None else interpret
    m, k = a.shape
    _, n = b.shape
    ka, kb = jax.random.split(key)
    ap, _ = _pad_to(a, block[0], block[2])
    bp, _ = _pad_to(b, block[2], block[1])
    ua = jax.random.uniform(ka, ap.shape, jnp.float32)
    ub = jax.random.uniform(kb, bp.shape, jnp.float32)
    alpha_a = jnp.max(jnp.abs(a.astype(jnp.float32)))
    alpha_b = jnp.max(jnp.abs(b.astype(jnp.float32)))
    out = quant_matmul(ap, bp, ua, ub, alpha_a, alpha_b, block=block,
                       interpret=interpret)
    return out[:m, :n]


# Largest row count the fused ghost-norm kernel accepts: its two (T, T)
# f32 Gram scratches must fit VMEM alongside the operand blocks
# (2 * 512^2 * 4B = 2 MiB scratch + ~2 MiB blocks, well under the
# ~16 MiB/core budget).  Above the cap the wrapper falls back to the
# unfused quantize-then-Gram composition, which XLA handles at any size.
GHOST_NORM_MAX_T = 512


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ghost_norm_sq(x: jax.Array, g: jax.Array, key_x: jax.Array,
                  key_g: jax.Array, block_d: int = 256,
                  interpret=None) -> jax.Array:
    """Fused LUQ-quantize + Gram + tap-reduce: ``||Q(x)^T Q(g)||_F^2``.

    ``x``: (T, Din) wgrad-GEMM input rows; ``g``: (T, Dout) cotangent rows
    (the matrix views of the ghost einsum hook — contiguous reshapes of
    the original operands, so ``luq_uniform`` over the matrix view is
    elementwise identical to the draws ``luq_quantize`` makes for the
    original tensors with the same keys — the bit-parity contract with
    the pallas-backend vmap path).  Per-tensor alphas and uniform bits
    are computed on the unpadded operands; rows are zero-padded to a
    sublane multiple and both operands to one shared lane-aligned column
    count (zeros quantize to zero and contribute nothing to either Gram).
    """
    interpret = _interpret_default() if interpret is None else interpret
    t = x.shape[0]
    assert g.shape[0] == t, (x.shape, g.shape)
    if t > GHOST_NORM_MAX_T:
        # Gram scratch would not fit VMEM on a real TPU — unfused
        # composition, same keys/draws -> bit-identical result
        xq = luq_quantize(x, key_x).astype(jnp.float32)
        gq = luq_quantize(g, key_g).astype(jnp.float32)
        return jnp.vdot(xq @ xq.T, gq @ gq.T)
    ux = luq_uniform(key_x, x.shape)
    ug = luq_uniform(key_g, g.shape)
    alpha_x = jnp.max(jnp.abs(x.astype(jnp.float32))).reshape(1, 1)
    alpha_g = jnp.max(jnp.abs(g.astype(jnp.float32))).reshape(1, 1)
    d = max(x.shape[1], g.shape[1])
    d = d + ((-d) % block_d)
    pt = (-t) % 8

    def pad(a):
        return jnp.pad(a.astype(jnp.float32),
                       ((0, pt), (0, d - a.shape[1])))

    out = ghost_norm_gram(pad(x), pad(ux), pad(g), pad(ug), alpha_x,
                          alpha_g, block_d=block_d, interpret=interpret)
    return out[0, 0]


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows",
                                             "interpret"))
def kv_quant_rows(x: jax.Array, fmt: str, block_rows: int = 128,
                  interpret=None):
    """Fused KV-row quantization of ``(..., head_dim)`` K/V rows.

    Returns ``(codes, scales)`` exactly like the ref
    ``repro.quant.kv_cache.kv_quant``: codes ``(..., code_dim)`` (int8, or
    nibble-packed uint8 for luq_fp4) and per-row bf16 scales ``(...,)``.
    The kernel computes the per-row amax, the bf16-rounded scale, and the
    codes in one VMEM pass per row block; rows are padded to a
    ``block_rows`` multiple and head_dim to a lane multiple (zero columns
    never raise a nonzero row's amax, and all-zero pad rows get scale 0).
    Deterministic, so it is bit-compatible with the ref impl by
    construction — both encode with the shared elementwise math in
    ``repro.quant.kv_cache``.
    """
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    hd = shape[-1]
    _, code_dim = kvc.code_spec(fmt, hd)
    rows = x.reshape(-1, hd).astype(jnp.float32)
    r = rows.shape[0]
    pr = (-r) % block_rows
    pd = (-hd) % 128
    if pr or pd:
        rows = jnp.pad(rows, ((0, pr), (0, pd)))
    codes, scales = kv_rowquant_2d(rows, fmt, block_rows=block_rows,
                                   interpret=interpret)
    codes = codes[:r, :hd]
    scales = scales[:r, 0].astype(kvc.SCALE_DTYPE)
    if fmt == "luq_fp4":
        codes = kvc.fp4_pack(codes.astype(jnp.uint8))
    return (codes.reshape(shape[:-1] + (code_dim,)),
            scales.reshape(shape[:-1]))


@functools.partial(jax.jit, static_argnames=("fmt", "n_kv", "scale",
                                             "interpret"))
def decode_attn_fused(q: jax.Array, k_codes: jax.Array, v_codes: jax.Array,
                      k_scale: jax.Array, v_scale: jax.Array, pos, *,
                      fmt: str, n_kv: int, scale: float, interpret=None):
    """Fused decode attention over a quantized slot-pool cache.

    Same signature/semantics as ``repro.quant.kv_cache.ref_decode_attn``
    for the quantized formats: ``q`` (B, H, hd), stored code rows
    (B, KV, S, code_dim) with (B, KV, S) bf16 scales, ``pos`` scalar or
    (B,) per-slot positions.  One VMEM pass per (slot, kv-head): decode,
    scale-fold, mask, softmax, PV (``repro.kernels.decode_attn``).
    Padding: q-head groups to a sublane multiple, head_dim (packed dim
    for luq_fp4) to a lane multiple, S to a sublane multiple — padded
    rows carry zero codes/scales and masked positions, contributing
    exactly zero.
    """
    interpret = _interpret_default() if interpret is None else interpret
    b, hp, hd = q.shape
    g = hp // n_kv
    s = k_codes.shape[2]
    dp = k_codes.shape[3]
    # luq_fp4 keeps its packed dim: q and the context travel as two planes
    # (even / odd head_dim indices), matching the low / high nibbles
    planes = 2 if fmt == "luq_fp4" else 1
    pad_dp = (-dp) % (64 if fmt == "luq_fp4" else 128)
    pg, ps = (-g) % 8, (-s) % 8
    qg = q.reshape(b, n_kv, g, hd).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pg),
                      (0, planes * (dp + pad_dp) - hd)))
    qg = qg.reshape(b, n_kv, g + pg, dp + pad_dp, planes)
    qg = qg.transpose(0, 1, 4, 2, 3)
    kc = jnp.pad(k_codes, ((0, 0), (0, 0), (0, ps), (0, pad_dp)))
    vc = jnp.pad(v_codes, ((0, 0), (0, 0), (0, ps), (0, pad_dp)))
    ks = jnp.pad(k_scale.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, ps)))[:, :, None, :]
    vs = jnp.pad(v_scale.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, ps)))[:, :, None, :]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    ctx = decode_attn_call(qg, kc, ks, vc, vs, pos_b, fmt=fmt, scale=scale,
                           interpret=interpret)
    ctx = ctx.transpose(0, 1, 3, 4, 2).reshape(b, n_kv, g + pg, -1)
    return ctx[:, :, :g, :hd].reshape(b, hp, hd)


@functools.partial(jax.jit, static_argnames=("clip_norm", "block_d",
                                             "interpret"))
def clip_and_sum(grads: jax.Array, clip_norm: float, block_d: int = 512,
                 interpret=None):
    """Fused DP per-example clip + batch sum.

    ``grads``: (B, D) per-example gradient rows, any float dtype, any B >= 1
    and D >= 1 (D is zero-padded to a ``block_d`` multiple internally —
    zero columns change neither the row norms nor the sum, and the padding
    is stripped before returning).

    Returns ``(clipped_sum, norms)`` matching ``ref.per_sample_clip_ref``:
    ``clipped_sum`` (D,) f32 = sum_b min(1, C/||g_b||) * g[b], and ``norms``
    (B,) f32 per-example l2 norms (the clip-fraction / grad-norm
    diagnostics of paper Fig. 1c are computed from these).
    """
    interpret = _interpret_default() if interpret is None else interpret
    b, d = grads.shape
    pd = (-d) % block_d
    if pd:
        grads = jnp.pad(grads, ((0, 0), (0, pd)))
    out, norms = per_sample_clip(grads, clip_norm, block_d=block_d,
                                 interpret=interpret)
    return out[:d], norms
