"""Quantizer-backend dispatch: (op, format) -> implementation registry.

The quantization stack has two execution backends:

``"ref"``     the pure-jnp quantizers in ``repro.quant.formats`` (default;
              runs everywhere, the numerical reference),
``"pallas"``  the fused Pallas TPU kernels wrapped in ``repro.kernels.ops``
              (compiled on a TPU, interpret mode on any other backend).

Three ops are dispatched:

``"quantize"``  ``q(x, key) -> x_q`` — elementwise fake-quantization, the
                primitive behind ``fake_quant.qeinsum``/``qconv2d``.
``"matmul"``    ``mm(a, b, key) -> f32`` — quantize-both-operands matmul
                (serving hot path); the pallas impl quantizes tiles in VMEM
                fused with the MXU contraction (zero extra HBM traffic).
``"clip_sum"``  ``cs(grads, clip_norm) -> (clipped_sum, norms)`` — fused DP
                per-example clip + batch sum over (B, D) gradient rows;
                format-agnostic (registered under fmt ``"*"``).
``"ghost_norm"`` ``gn(xmat, gmat, key_x, key_g) -> f32 scalar`` — the ghost
                clipping tap ``||Q(x)^T Q(g)||_F^2`` from the (T, Din) /
                (T, Dout) wgrad-GEMM matrix views; the pallas impl fuses
                quantize + Gram + tap-reduce into one VMEM pass
                (``repro.kernels.ghost_norm``), the ref impl composes the
                quantizer with the mixed-ghost-norm reduction.
``"kv_quant"``  ``kvq(x) -> (codes, scales)`` — deterministic per-row
                quantization of written K/V cache rows (serve path;
                formats are the KV *storage* formats ``none|int8|luq_fp4``
                of ``repro.quant.kv_cache``, not the training formats);
                the pallas impl fuses amax + scale + encode into one VMEM
                pass per row block (``repro.kernels.decode_attn``).
``"decode_attn"`` ``attn(q, kc, vc, ks, vs, pos, *, n_kv, scale) -> ctx``
                — one-token GQA attention over the quantized slot-pool
                cache; the pallas impl fuses dequantization into the QK
                and PV contractions with per-slot position masking and
                softmax in one VMEM pass per (slot, kv-head); the ref
                impl dequantizes and runs the plain-jnp attention (for
                ``none`` it IS the historical ``decode_attend`` math,
                bit-for-bit).

Backend selection: the ``REPRO_QUANT_BACKEND`` environment variable
overrides everything (so CI can force the pallas leg without touching
configs); otherwise the per-call request (``QuantConfig.backend``) wins;
otherwise ``"ref"``.  Formats a backend does not implement fall back to
``"ref"`` *explicitly*: ``get_*`` returns ``(impl, actual_backend)`` so
callers can see (and tests can assert) where an op really runs.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.quant import formats

ENV_VAR = "REPRO_QUANT_BACKEND"
DEFAULT_BACKEND = "ref"
BACKENDS = ("ref", "pallas")
OPS = ("quantize", "matmul", "clip_sum", "ghost_norm", "kv_quant",
       "decode_attn")

# fmt sentinel for format-agnostic ops (clip_sum)
ANY_FORMAT = "*"

# (op, fmt, backend) -> impl
_REGISTRY: Dict[Tuple[str, str, str], Callable] = {}


def register(op: str, fmt: str, backend: str, impl: Callable) -> None:
    if op not in OPS:
        raise ValueError(f"unknown op {op!r} (expected one of {OPS})")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    _REGISTRY[(op, fmt, backend)] = impl


def _lookup(op: str, fmt: str, backend: str):
    impl = _REGISTRY.get((op, fmt, backend))
    if impl is None:
        impl = _REGISTRY.get((op, ANY_FORMAT, backend))
    return impl


def supported(op: str, fmt: str, backend: str) -> bool:
    """Capability check: does ``backend`` natively implement (op, fmt)?"""
    return _lookup(op, fmt, backend) is not None


def resolve_backend(requested: str | None = None) -> str:
    """Concrete backend name: env override > request > default."""
    backend = os.environ.get(ENV_VAR) or requested or DEFAULT_BACKEND
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown quant backend {backend!r} (expected one of {BACKENDS}; "
            f"check {ENV_VAR} / QuantConfig.backend)")
    return backend


def get_impl(op: str, fmt: str, backend: str | None = None):
    """Resolve (op, fmt) on ``backend`` with explicit ref fallback.

    Returns ``(impl, actual_backend)``; ``actual_backend`` differs from the
    request when the backend lacks the format and ``"ref"`` filled in.
    """
    be = resolve_backend(backend)
    impl = _lookup(op, fmt, be)
    if impl is None and be != DEFAULT_BACKEND:
        impl, be = _lookup(op, fmt, DEFAULT_BACKEND), DEFAULT_BACKEND
    if impl is None:
        raise KeyError(f"no implementation for op={op!r} fmt={fmt!r} "
                       f"on any backend")
    return impl, be


def get_quantizer(fmt: str, backend: str | None = None):
    """``(q(x, key) -> x_q, actual_backend)``."""
    return get_impl("quantize", fmt, backend)


def get_matmul(fmt: str, backend: str | None = None):
    """``(mm(a, b, key) -> (M, N) f32, actual_backend)``."""
    return get_impl("matmul", fmt, backend)


def get_kv_quant(fmt: str, backend: str | None = None):
    """``(kvq(x) -> (codes, scales), actual_backend)`` — KV cache rows.

    ``fmt`` is a KV *storage* format (``repro.config.KV_CACHE_FORMATS``),
    orthogonal to the training formats the other ops use.
    """
    return get_impl("kv_quant", fmt, backend)


def get_decode_attn(fmt: str, backend: str | None = None):
    """``(attn(q, kc, vc, ks, vs, pos, *, n_kv, scale), actual_backend)``."""
    return get_impl("decode_attn", fmt, backend)


def get_clip_sum(backend: str | None = None):
    """``(cs(grads, clip_norm) -> (clipped_sum, norms), actual_backend)``.

    Accepts the DPConfig spelling ``"fused"`` as an alias for ``"pallas"``.
    Unlike the quantize/matmul ops, ``REPRO_QUANT_BACKEND`` does NOT apply
    here: the clip implementation is its own knob (``DPConfig.clip_backend``)
    and an explicit ``"fused"`` request must not be silently downgraded by
    an env var meant to pin the quantizers.
    """
    if backend == "fused":
        backend = "pallas"
    be = backend or DEFAULT_BACKEND
    if be not in BACKENDS:
        raise ValueError(f"unknown clip backend {be!r} "
                         f"(expected one of {BACKENDS})")
    impl = _lookup("clip_sum", ANY_FORMAT, be)
    if impl is None:
        raise KeyError(f"no clip_sum implementation on backend {be!r}")
    return impl, be


def capability_table() -> Dict[str, Dict[str, Tuple[str, ...]]]:
    """{op: {backend: (natively supported formats...)}} — docs/tests."""
    table: Dict[str, Dict[str, list]] = {op: {b: [] for b in BACKENDS}
                                         for op in OPS}
    for (op, fmt, backend) in _REGISTRY:
        table[op][backend].append(fmt)
    return {op: {b: tuple(sorted(fmts)) for b, fmts in row.items()}
            for op, row in table.items()}


# --------------------------------------------------------------------------- #
# ref backend: the pure-jnp formats (every format, every op)
# --------------------------------------------------------------------------- #
def _ref_matmul(fmt: str) -> Callable:
    q = formats.make_quantizer(fmt)

    def mm(a, b, key):
        ka, kb = jax.random.split(key)
        aq = q(a, ka).astype(jnp.float32)
        bq = q(b, kb).astype(jnp.float32)
        return aq @ bq

    return mm


def _ref_clip_sum(grads, clip_norm):
    from repro.kernels.ref import per_sample_clip_ref
    return per_sample_clip_ref(grads, clip_norm)


def _ref_ghost_norm(fmt: str) -> Callable:
    q = formats.make_quantizer(fmt)

    def gn(xmat, gmat, key_x, key_g):
        # lazy: dp.ghost imports this module only inside functions, so the
        # package stays import-order independent
        from repro.dp.ghost import _matpair_sq_norm
        return _matpair_sq_norm(q(xmat, key_x), q(gmat, key_g))

    return gn


def _ref_kv_quant(fmt: str) -> Callable:
    def kvq(x):
        from repro.quant import kv_cache
        return kv_cache.kv_quant(fmt, x)

    return kvq


def _ref_decode_attn(fmt: str) -> Callable:
    def attn(q, kc, vc, ks, vs, pos, *, n_kv, scale):
        from repro.quant import kv_cache
        return kv_cache.ref_decode_attn(fmt, q, kc, vc, ks, vs, pos,
                                        n_kv=n_kv, scale=scale)

    return attn


for _fmt in formats._FORMATS:
    register("quantize", _fmt, "ref", formats.make_quantizer(_fmt))
    register("matmul", _fmt, "ref", _ref_matmul(_fmt))
    register("ghost_norm", _fmt, "ref", _ref_ghost_norm(_fmt))
register("clip_sum", ANY_FORMAT, "ref", _ref_clip_sum)
# KV-cache ops use the storage formats (repro.config.KV_CACHE_FORMATS),
# not the training formats above — "int8" exists only here.
for _fmt in ("none", "int8", "luq_fp4"):
    register("kv_quant", _fmt, "ref", _ref_kv_quant(_fmt))
    register("decode_attn", _fmt, "ref", _ref_decode_attn(_fmt))


# --------------------------------------------------------------------------- #
# pallas backend: the fused TPU kernels (LUQ-FP4 only; clip is any-format)
# --------------------------------------------------------------------------- #
# Kernel wrappers are imported lazily inside the impls: repro.kernels pulls
# repro.quant.formats back in, and deferring the import keeps package init
# order-independent.
def _pallas_quantize(x, key):
    from repro.kernels.ops import luq_quantize
    return luq_quantize(x, key)


def _pallas_matmul(a, b, key):
    from repro.kernels.ops import luq_matmul
    return luq_matmul(a, b, key)


def _pallas_clip_sum(grads, clip_norm):
    from repro.kernels.ops import clip_and_sum
    return clip_and_sum(grads, float(clip_norm))


def _pallas_ghost_norm(xmat, gmat, key_x, key_g):
    from repro.kernels.ops import ghost_norm_sq
    return ghost_norm_sq(xmat, gmat, key_x, key_g)


def _pallas_kv_quant(fmt: str) -> Callable:
    def kvq(x):
        from repro.kernels.ops import kv_quant_rows
        return kv_quant_rows(x, fmt)

    return kvq


def _pallas_decode_attn(fmt: str) -> Callable:
    def attn(q, kc, vc, ks, vs, pos, *, n_kv, scale):
        from repro.kernels.ops import decode_attn_fused
        return decode_attn_fused(q, kc, vc, ks, vs, pos, fmt=fmt,
                                 n_kv=n_kv, scale=scale)

    return attn


register("quantize", "luq_fp4", "pallas", _pallas_quantize)
register("matmul", "luq_fp4", "pallas", _pallas_matmul)
register("clip_sum", ANY_FORMAT, "pallas", _pallas_clip_sum)
register("ghost_norm", "luq_fp4", "pallas", _pallas_ghost_norm)
# kv_fmt="none" has no fused kernel (there is nothing to dequantize);
# it falls back to ref explicitly via get_impl, like every missing format
for _fmt in ("int8", "luq_fp4"):
    register("kv_quant", _fmt, "pallas", _pallas_kv_quant(_fmt))
    register("decode_attn", _fmt, "pallas", _pallas_decode_attn(_fmt))
