"""Pallas TPU kernel: fused ghost-norm (quantize + Gram + tap-reduce).

The ghost-clipping norm pass needs, per hooked layer and example, the
squared Frobenius norm of the quantized wgrad GEMM

    || Q(x)^T Q(g) ||_F^2  =  < Q(x) Q(x)^T , Q(g) Q(g)^T >

(the Gram route of the mixed ghost norm).  As three XLA ops this is two
elementwise quantize dispatches (each an HBM round-trip of the operand)
plus the Gram/contract einsums.  The fused kernel streams each (T, bd)
column block of x and g through VMEM exactly once: the block is LUQ-
quantized in registers (``luq_stochastic_round`` — the same math as the
quantize kernel, so bits cannot drift), its (T, T) Gram outer-product is
accumulated into a VMEM scratch, and the final grid step reduces the two
Grams to the scalar tap with one vdot.  Quantized operands never touch
HBM.

Both operands are padded to a SHARED column-block count (zero columns
change neither Gram), so one grid axis drives both accumulations.  VMEM
holds two (T, T) f32 scratches — the caller only selects this kernel
when the Gram route wins (T^2 <= Din*Dout), which bounds T^2 by the
layer's weight size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.luq_quant import luq_stochastic_round


def _ghost_norm_kernel(x_ref, ux_ref, g_ref, ug_ref, ax_ref, ag_ref,
                       o_ref, xx_ref, gg_ref):
    j = pl.program_id(0)
    nj = pl.num_programs(0)

    @pl.when(j == 0)
    def _():
        xx_ref[...] = jnp.zeros_like(xx_ref)
        gg_ref[...] = jnp.zeros_like(gg_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = luq_stochastic_round(x_ref[...].astype(jnp.float32),
                              ux_ref[...], ax_ref[0, 0])
    gq = luq_stochastic_round(g_ref[...].astype(jnp.float32),
                              ug_ref[...], ag_ref[0, 0])
    xx_ref[...] += jax.lax.dot_general(
        xq, xq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    gg_ref[...] += jax.lax.dot_general(
        gq, gq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        # a (1, 1) vector store: Mosaic cannot store a scalar to VMEM
        o_ref[...] = jnp.sum(xx_ref[...] * gg_ref[...], keepdims=True)


def ghost_norm_gram(x: jax.Array, ux: jax.Array, g: jax.Array,
                    ug: jax.Array, alpha_x: jax.Array, alpha_g: jax.Array,
                    block_d: int = 256, interpret: bool = False) -> jax.Array:
    """x, ux: (T, D); g, ug: (T, D) — both padded to the same T (8-mult)
    and D (block_d-mult) by the wrapper; alphas: scalars.  Returns the
    (1, 1) f32 tap value ``<Q(x)Q(x)^T, Q(g)Q(g)^T>``."""
    t, d = x.shape
    assert g.shape == (t, d) and d % block_d == 0, (x.shape, g.shape)
    bd = block_d
    out = pl.pallas_call(
        _ghost_norm_kernel,
        grid=(d // bd,),
        in_specs=[
            pl.BlockSpec((t, bd), lambda j: (0, j)),
            pl.BlockSpec((t, bd), lambda j: (0, j)),
            pl.BlockSpec((t, bd), lambda j: (0, j)),
            pl.BlockSpec((t, bd), lambda j: (0, j)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32),
                        pltpu.VMEM((t, t), jnp.float32)],
        interpret=interpret,
        name="ghost_norm",
    )(x, ux, g, ug, alpha_x, alpha_g)
    return out
