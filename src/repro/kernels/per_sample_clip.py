"""Pallas TPU kernel: fused per-example clip + batch reduction.

The DP-SGD inner loop reduces, for each example b, its gradient row to an
l2 norm, rescales to norm <= C, and sums over the batch:

    out[d] = sum_b min(1, C / ||g_b||) * g[b, d]

Doing this as three XLA ops re-reads the (B, D) gradient matrix from HBM
twice.  The fused kernel streams each (B, bd) column block once:

  pass 1 (grid dim 0): accumulate per-example partial square sums in a VMEM
     scratch (B, 1);
  pass 2 (grid dim 0 again, second grid axis selects the phase): apply
     min(1, C/norm) and accumulate the weighted column sums.

Implemented as a 2-phase grid: phase 0 only touches the square-sum scratch;
phase 1 re-reads the block (still VMEM-resident for small B*bd) and writes
the clipped sum.  Norms are emitted for the clip-fraction diagnostics
(paper Fig. 1c).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _clip_kernel(g_ref, o_ref, norms_ref, sq_ref, *, n_cols, clip_norm):
    phase = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((phase == 0) & (j == 0))
    def _():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    g = g_ref[...].astype(jnp.float32)

    @pl.when(phase == 0)
    def _():
        sq_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)

    @pl.when(phase == 1)
    def _():
        norms = jnp.sqrt(sq_ref[...])
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        o_ref[...] = jnp.sum(g * scale, axis=0, keepdims=True)
        @pl.when(j == n_cols - 1)
        def _():
            norms_ref[...] = norms

    # keep outputs defined in phase 0 as well (same blocks revisited)
    @pl.when((phase == 0) & (j == 0))
    def _():
        norms_ref[...] = jnp.zeros_like(norms_ref)

    @pl.when(phase == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def per_sample_clip(grads: jax.Array, clip_norm: float, block_d: int = 512,
                    interpret: bool = False):
    """grads: (B, D) per-example gradient rows.

    Returns (clipped_sum (D,), norms (B,)).  D % block_d == 0 required
    (pad upstream); B must fit a VMEM tile (true for microbatch sizes).
    """
    b, d = grads.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    n_cols = d // bd
    out, norms = pl.pallas_call(
        functools.partial(_clip_kernel, n_cols=n_cols, clip_norm=clip_norm),
        grid=(2, n_cols),
        in_specs=[pl.BlockSpec((b, bd), lambda p, j: (0, j))],
        out_specs=[pl.BlockSpec((1, bd), lambda p, j: (0, j)),
                   pl.BlockSpec((b, 1), lambda p, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.float32)],
        interpret=interpret,
        name="per_sample_clip",
    )(grads)
    return out[0], norms[:, 0]
