"""Logical axis names + an ambient constraint context.

Models annotate activations with *logical* names; when a partitioning context
is active (set by launch/steps.py under a mesh) the names resolve to
``jax.lax.with_sharding_constraint``; otherwise they are no-ops, so the same
model code runs unsharded on CPU tests.  ``storage_layout`` pins an array to
the physical layout its device stores such an array in.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
from jax._src import core
from jax._src.interpreters import mlir
from jax._src.layout import Layout

# canonical logical axes
BATCH = "batch"
SEQ = "seq"
EMBED = "embed"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
MLP = "mlp"
VOCAB = "vocab"
EXPERTS = "experts"
EXPERT_MLP = "expert_mlp"
LAYERS = "layers"
KV_SEQ = "kv_seq"
STATE = "state"
CONV = "conv"
POD_CHUNK = "pod_chunk"

_ctx = threading.local()


@contextlib.contextmanager
def partitioning_context(resolver):
    """``resolver(logical_names) -> NamedSharding`` or None."""
    prev = getattr(_ctx, "resolver", None)
    _ctx.resolver = resolver
    try:
        yield
    finally:
        _ctx.resolver = prev


def logical_constraint(x: jax.Array, *names: Optional[str]) -> jax.Array:
    resolver = getattr(_ctx, "resolver", None)
    if resolver is None:
        return x
    sharding = resolver(tuple(names), x.shape)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _storage_layout_lowering(ctx, x):
    aval, = ctx.avals_in
    client = ctx.module_context.backend
    layout = Layout.from_pjrt_layout(client.get_default_layout(
        aval.dtype, aval.shape, client.devices()[0]))
    return [mlir.wrap_with_layout_op(ctx, x, aval, layout, aval)]


_storage_layout_p = core.Primitive("storage_layout")
_storage_layout_p.def_impl(lambda x: x)
_storage_layout_p.def_abstract_eval(lambda x: x)
mlir.register_lowering(_storage_layout_p, _storage_layout_lowering)


def storage_layout(x: jax.Array) -> jax.Array:
    """``x`` unchanged, laid out as the compile target's device stores an
    array of its shape and dtype.

    A TPU stores an array whose minor dim is not a multiple of 128 lanes
    with its two minor dims swapped (a (..., 2048, 80) KV cache keeps the
    2048 positions minor).  A loop that carries such an array and hands
    slices of it to a kernel taking row-major operands otherwise gets the
    carry laid out for the kernel, and XLA copies the whole array into
    and out of that layout around the loop.
    """
    return _storage_layout_p.bind(x)
