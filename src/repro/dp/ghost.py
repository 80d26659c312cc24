"""Ghost-norm two-pass DP-SGD gradient engine (``DPConfig.grad_mode="ghost"``).

The vmap path (``repro.dp.clip``) materializes a full per-example gradient
pytree via ``vmap(grad)``: O(B x params) live memory and B independent
rank-1/rank-T weight-grad contractions per layer instead of one batched
GEMM.  Ghost clipping removes both costs without changing the numbers:

pass 1 — norms
    One vmapped forward+backward in which every *hooked* layer (the
    ``qeinsum`` projections and ``qconv2d`` convolutions the models already
    thread through ``repro.quant.fake_quant``) contributes its per-example
    squared weight-grad norm to a scalar "tap" input through a custom VJP,
    without ever forming the per-example weight grad:

        || x^T g ||_F^2  =  < x x^T , g g^T >        (Gram identity)

    computed as two (T, T) Grams (T = tokens/pixels per example) when
    T^2 < |w|, or as the direct (din, dout) contraction followed by an
    immediate square-reduce when the layer is small (mixed ghost norm).
    On ``backend="pallas"`` with a quantized wgrad the Gram route runs as
    ONE fused Pallas ``ghost_norm`` kernel (quantize + Gram + tap-reduce in
    a single VMEM pass — see ``repro.kernels.ghost_norm``), dispatched
    through ``repro.quant.backend``.

    Leaves not covered by a hook fall back to a vmapped *norm-only*
    per-example grad restricted to those leaves (hooked wgrads are
    DCE'd).  Dense LMs need no fallback at all: norm scales are tapped by
    a ghost ``rmsnorm`` hook, and the embedding/LM head are covered by the
    model-supplied :class:`GhostAux` hooks — a gather-side hook (token-
    equality-masked Gram of the lookup cotangents) plus a single-chunk
    LM-head hook, including the gather-head *cross term* tied embeddings
    require (the two contributions land on the same leaf, so
    ``||d_gather + d_head||^2`` has a ``2<d_gather, d_head>`` term that
    per-op scalar taps cannot see).

pass 2 — grads
    ``jax.grad`` of the scale-reweighted per-example-loss sum
    ``sum_i scale_i * loss_i`` over the *batched* (not vmapped) model:
    one standard backward at full arithmetic intensity — each layer's
    weight grad is a single (B*T, din) x (B*T, dout) GEMM that directly
    yields the clipped gradient **sum**.

Memory/scale controls
---------------------
``ghost_microbatch`` chunks pass 1 with a ``lax.scan`` over fixed-size
example chunks (tap accumulation per chunk), so pass-1 live state is one
chunk of activations instead of the whole batch — pass 2 stays one fused
batched backward, leaving its activations as the only batch-scaling
memory term (the profile of non-DP training).

``sharded_ghost_clipped_grad_sum`` is the data-parallel formulation: a
``shard_map`` over the mesh's data axes where each shard computes
per-shard squared-norm taps and its local reweighted backward, combined
by ONE ``psum`` of the clipped grad sums (norms/losses are all-gathered
for the metrics contract).

Quantization parity
-------------------
The vmap path applies each stochastic quantizer per example (a (1, ...)
tensor per vmap lane, per-tensor max scaling, and an unbatched key whose
uniform draw is hoisted across lanes).  Pass 2 reproduces this exactly in
batched form: under the ghost grad context, ``fake_quant`` quantizes the
batched activation/cotangent operands *per example* (``jax.vmap`` of the
backend quantizer over example slices with the shared key — identical
draws, per-example alpha).  Because LUQ/INT4 use per-tensor max scaling
they are exactly positively-scale-invariant, so quantizing the
scale-reweighted cotangent equals reweighting the quantized cotangent:

    Q(scale_i * g_i) = scale_i * Q(g_i)

which is what makes the one-backward reweighting produce the same clipped
sums as the vmap path to fp32 tolerance *with stochastic quantization
enabled*.  Per-example quantization is also chunk-invariant, which is why
``ghost_microbatch`` and the sharded driver leave the numbers unchanged.
Deterministic relative-rounding formats (fp8/bf16) are only approximately
scale-invariant (deviation bounded by the format's relative precision);
``none`` is exact.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.tracing import scope


# --------------------------------------------------------------------------- #
# trace-time context: which ghost pass (if any) the model is being traced for
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _NormCtx:
    """Pass 1: hooked ops add per-example squared norms to ``tap``.

    ``norm_scales`` opts the norm-scale hooks (``ghost_scale_norm`` via
    ``models/common.rmsnorm``) into the tap: only drivers whose hooked
    mask actually marks the scale leaves may enable it, otherwise the
    vmapped fallback would double-count them.
    """
    tap: jax.Array
    mode: str = "norm"
    norm_scales: bool = False


@dataclasses.dataclass
class _GradCtx:
    """Pass 2: quantizers switch to per-example (vmap-parity) semantics."""
    mode: str = "grad"


_STACK: List[object] = []


def current():
    """The active ghost context (or None) — consulted by fake_quant at
    trace time; the returned context's behavior is baked into the traced
    custom-VJP statics, so backward traces never re-read it."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def norm_pass(tap: jax.Array, norm_scales: bool = False):
    _STACK.append(_NormCtx(tap=tap, norm_scales=norm_scales))
    try:
        yield
    finally:
        _STACK.pop()


@contextlib.contextmanager
def grad_pass():
    _STACK.append(_GradCtx())
    try:
        yield
    finally:
        _STACK.pop()


# --------------------------------------------------------------------------- #
# model-supplied auxiliary hooks (embedding / LM head coverage)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class GhostAux:
    """Extra pass-1 hooks for leaves whose per-example norm needs more than
    a per-op scalar tap (gather-scattered embeddings, loss-side heads, and
    — for tied embeddings — their cross term).

    ``make_taps(example) -> pytree``
        zero arrays injected additively into the model's dataflow (e.g. at
        the embedding-gather output and the single-chunk logits); their
        cotangents under ``jax.grad`` ARE the quantities the norms need.
    ``tapped_loss(params, example, rng, taps) -> (loss, fwd_aux)``
        the per-example loss with the taps injected; ``fwd_aux`` carries
        forward values the combine step needs (e.g. the final hidden rows).
    ``combine(tap_cots, fwd_aux, example) -> scalar``
        the extra per-example squared-norm contribution of the
        aux-covered leaves.
    ``covers(params) -> bool pytree``
        leaves covered by the aux hooks (and the norm-scale hooks when
        ``hook_norm_scales``); OR-ed into the driver's hooked mask.
    """
    make_taps: Callable
    tapped_loss: Callable
    combine: Callable
    covers: Callable
    hook_norm_scales: bool = False


def effective_hooked_mask(params, hooked_mask, aux: Optional[GhostAux]):
    """The op-level hook mask OR the aux-covered leaves."""
    if aux is None:
        return hooked_mask
    return jax.tree_util.tree_map(lambda a, b: bool(a) or bool(b),
                                  hooked_mask, aux.covers(params))


# --------------------------------------------------------------------------- #
# per-example squared weight-grad norms (the "ghost" in ghost clipping)
# --------------------------------------------------------------------------- #
def gram_route_wins(t: int, din: int, dout: int) -> bool:
    """The mixed-ghost-norm route rule, in ONE place: Gram when T^2 is no
    larger than the weight (direct-product) size.  Shared by
    ``_matpair_sq_norm``, the fused-kernel dispatch in ``_tap_sq_norm``
    (the pallas kernel implements only the Gram route), and the ref
    ``ghost_norm`` backend impl — so the three can never disagree."""
    return t * t <= din * dout


def _matpair_sq_norm(xmat: jax.Array, gmat: jax.Array) -> jax.Array:
    """||xmat^T gmat||_F^2 without materializing it when Grams are cheaper.

    ``xmat``: (T, Din) wgrad-GEMM input rows; ``gmat``: (T, Dout) output
    cotangent rows.  Static shape-based choice (mixed ghost norm): Gram
    route costs O(T^2 (Din + Dout)) and peaks at two (T, T) buffers; the
    direct route costs the plain wgrad GEMM but its (Din, Dout) product is
    consumed by an immediate square-reduce (transient, fuses under XLA).
    """
    xmat = xmat.astype(jnp.float32)
    gmat = gmat.astype(jnp.float32)
    if gram_route_wins(xmat.shape[0], xmat.shape[1], gmat.shape[1]):
        return jnp.vdot(xmat @ xmat.T, gmat @ gmat.T)
    dw = xmat.T @ gmat
    return jnp.sum(dw * dw)


@functools.lru_cache(maxsize=None)
def _spec_axes(spec: str) -> Tuple[str, str, str, str, str, str]:
    """Split an einsum spec into (x_term, w_term, out_term, T, din, dout).

    T = x dims not contracted into w (batch/seq/pixels), din = x dims
    shared with w, dout = w dims appearing in the output.  Covers every
    projection spec the models use (no repeated or elided letters).
    """
    lhs, out_term = spec.replace(" ", "").split("->")
    x_term, w_term = lhs.split(",")
    t_ax = "".join(c for c in x_term if c not in w_term)
    din = "".join(c for c in x_term if c in w_term)
    dout = "".join(c for c in w_term if c not in x_term)
    if set(t_ax) - set(out_term) or set(dout) - set(out_term):
        raise ValueError(f"einsum spec {spec!r} is not a ghost-hookable "
                         f"projection (x-batch or w-out dims missing from "
                         f"the output)")
    return x_term, w_term, out_term, t_ax, din, dout


def _einsum_matviews(spec: str, x: jax.Array, g: jax.Array):
    """(xmat (T, Din), gmat (T, Dout), contiguous) matrix views of the
    wgrad-GEMM operands.  ``contiguous`` is True when both views are pure
    reshapes (no axis permutation) — the condition under which uniform
    draws over the matrix view match draws over the original tensors
    elementwise (the fused-kernel RNG-parity requirement)."""
    x_term, _, out_term, t_ax, din, dout = _spec_axes(spec)
    sizes = {**dict(zip(x_term, x.shape)), **dict(zip(out_term, g.shape))}
    xmat = jnp.einsum(f"{x_term}->{t_ax}{din}", x).reshape(
        int(np.prod([sizes[c] for c in t_ax], initial=1)),
        int(np.prod([sizes[c] for c in din], initial=1)))
    gmat = jnp.einsum(f"{out_term}->{t_ax}{dout}", g).reshape(
        int(np.prod([sizes[c] for c in t_ax], initial=1)),
        int(np.prod([sizes[c] for c in dout], initial=1)))
    contiguous = (x_term == t_ax + din) and (out_term == t_ax + dout)
    return xmat, gmat, contiguous


def _einsum_sq_norm(spec: str, xq: jax.Array, gq: jax.Array) -> jax.Array:
    """Per-example ||dw||^2 of ``out = einsum(spec, x, w)`` from the wgrad
    GEMM inputs (already quantized when q_wgrad is on)."""
    xmat, gmat, _ = _einsum_matviews(spec, xq, gq)
    return _matpair_sq_norm(xmat, gmat)


def _tap_sq_norm(spec: str, x, g, seed, flag, fmt: str, q_wgrad: bool,
                 backend: str) -> jax.Array:
    """The per-example squared wgrad norm a ghost einsum hook emits.

    Quantization semantics are identical to the wgrad GEMM inputs
    (folds 4/5).  When the resolved backend natively implements the
    ``ghost_norm`` op for ``fmt`` (pallas: luq_fp4), the matrix views are
    contiguous, and the Gram route wins, the quantize + Gram + reduce
    chain collapses into the fused kernel — gated behind the same traced
    ``flag`` as ``_maybe_quant`` so DPQuant policy flips never recompile.
    """
    from repro.quant import backend as qbackend
    from repro.quant.fake_quant import _maybe_quant

    xmat, gmat, contiguous = _einsum_matviews(spec, x, g)
    gram_route = gram_route_wins(xmat.shape[0], xmat.shape[1],
                                 gmat.shape[1])
    if q_wgrad and fmt != "none":
        impl, actual = qbackend.get_impl("ghost_norm", fmt, backend)
        if actual != "ref" and contiguous and gram_route:
            kx = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), seed), 4)
            kg = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), seed), 5)
            return jax.lax.cond(
                flag > 0.5,
                lambda: impl(xmat, gmat, kx, kg),
                lambda: _matpair_sq_norm(xmat, gmat))
    xq = _maybe_quant(x, seed, 4, fmt, flag, backend) if q_wgrad else x
    gq = _maybe_quant(g, seed, 5, fmt, flag, backend) if q_wgrad else g
    return _einsum_sq_norm(spec, xq, gq)


# --------------------------------------------------------------------------- #
# ghost-hooked primitives (pass 1): qeinsum / qconv2d clones whose backward
# also emits the per-example squared wgrad norm as the tap cotangent
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def make_ghost_qeinsum(spec: str, fmt: str, q_fwd: bool, q_dgrad: bool,
                       q_wgrad: bool, backend: str):
    """Ghost-tapped variant of ``fake_quant._make_qeinsum``.

    Forward/dgrad/wgrad quantization is identical to the plain qeinsum
    (same folds, same keys); the extra ``tap`` argument does not affect
    the output — its cotangent is *defined* to be the per-example squared
    wgrad norm, computed from the same Q(x, fold 4) / Q(g, fold 5) inputs
    the wgrad GEMM consumes, so pass-1 norms match the vmap path's norms
    of actually-quantized per-example grads.
    """
    from repro.quant.fake_quant import _maybe_quant

    def einsum(x, w):
        return jnp.einsum(spec, x, w)

    @jax.custom_vjp
    def gqeinsum(x, w, seed, flag, tap):
        del tap
        xq = _maybe_quant(x, seed, 0, fmt, flag, backend) if q_fwd else x
        wq = _maybe_quant(w, seed, 1, fmt, flag, backend) if q_fwd else w
        return einsum(xq, wq)

    def fwd(x, w, seed, flag, tap):
        return gqeinsum(x, w, seed, flag, tap), (x, w, seed, flag)

    def bwd(res, g):
        x, w, seed, flag = res
        wq = _maybe_quant(w, seed, 2, fmt, flag, backend) if q_dgrad else w
        gq_d = _maybe_quant(g, seed, 3, fmt, flag, backend) if q_dgrad else g
        (dx,) = jax.linear_transpose(lambda t: einsum(t, wq), x)(gq_d)
        xq = _maybe_quant(x, seed, 4, fmt, flag, backend) if q_wgrad else x
        gq_w = _maybe_quant(g, seed, 5, fmt, flag, backend) if q_wgrad else g
        # dw is only consumed when a caller differentiates the hooked
        # weight through a norm pass (pass 1 never does -> DCE'd by XLA)
        (dw,) = jax.linear_transpose(lambda t: einsum(xq, t), w)(gq_w)
        dtap = _tap_sq_norm(spec, x, g, seed, flag, fmt, q_wgrad, backend)
        return dx, dw, None, None, dtap

    gqeinsum.defvjp(fwd, bwd)
    return gqeinsum


@functools.lru_cache(maxsize=None)
def make_ghost_qconv(fmt: str, q_fwd: bool, q_dgrad: bool, q_wgrad: bool,
                     strides: tuple, padding: str, dnums_key: tuple,
                     filter_hw: tuple, backend: str,
                     rhs_dilation: tuple = (1, 1), feature_groups: int = 1):
    """Ghost-tapped variant of ``fake_quant._make_qconv`` (NHWC/HWIO).

    The per-example conv wgrad is ``patches(x)^T @ g`` (unfold-einsum):
    ``conv_general_dilated_patches`` with the conv's own strides/padding
    yields one (T, kh*kw*Cin) row per output position, aligned with the
    (T, Cout) cotangent rows, and the shared ``_matpair_sq_norm`` picks
    Gram vs direct per layer.

    Dilated (``rhs_dilation != (1, 1)``) and grouped
    (``feature_groups > 1``) convolutions are outside the patches
    identity; those layers fall back *per layer* to the direct norm of
    the per-example wgrad the backward already computes (``sum(dw^2)`` —
    exact, since pass 1 runs one example per vmap lane), instead of
    failing the whole family fast.
    """
    from repro.quant.fake_quant import _maybe_quant

    dn = jax.lax.ConvDimensionNumbers(*dnums_key)
    patches_ok = tuple(rhs_dilation) == (1, 1) and feature_groups == 1

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, strides, padding, rhs_dilation=rhs_dilation,
            dimension_numbers=dn, feature_group_count=feature_groups)

    @jax.custom_vjp
    def gqconv(x, w, seed, flag, tap):
        del tap
        xq = _maybe_quant(x, seed, 0, fmt, flag, backend) if q_fwd else x
        wq = _maybe_quant(w, seed, 1, fmt, flag, backend) if q_fwd else w
        return conv(xq, wq)

    def fwd(x, w, seed, flag, tap):
        return gqconv(x, w, seed, flag, tap), (x, w, seed, flag)

    def bwd(res, g):
        x, w, seed, flag = res
        wq = _maybe_quant(w, seed, 2, fmt, flag, backend) if q_dgrad else w
        gq_d = _maybe_quant(g, seed, 3, fmt, flag, backend) if q_dgrad else g
        (dx,) = jax.linear_transpose(lambda t: conv(t, wq), x)(gq_d)
        xq = _maybe_quant(x, seed, 4, fmt, flag, backend) if q_wgrad else x
        gq_w = _maybe_quant(g, seed, 5, fmt, flag, backend) if q_wgrad else g
        (dw,) = jax.linear_transpose(lambda t: conv(xq, t), w)(gq_w)
        if patches_ok:
            patches = jax.lax.conv_general_dilated_patches(
                xq, filter_hw, strides, padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            dtap = _matpair_sq_norm(patches.reshape(-1, patches.shape[-1]),
                                    gq_w.reshape(-1, gq_w.shape[-1]))
        else:
            # per-layer fallback: the backward's dw IS this example's
            # wgrad (one example per pass-1 vmap lane) — norm it directly
            dtap = jnp.sum(jnp.square(dw.astype(jnp.float32)))
        return dx, dw, None, None, dtap

    gqconv.defvjp(fwd, bwd)
    return gqconv


@functools.lru_cache(maxsize=None)
def make_ghost_scale_norm(base_fn: Callable, *static):
    """Ghost-tapped variant of an ``op(x, scale, *static)`` normalization.

    Output is bit-identical to ``base_fn``; the tap cotangent is the
    squared norm of the scale grad for the (single-example) call.  Used by
    ``models/common.rmsnorm`` when the active norm context enables
    ``norm_scales`` — per scan layer the contributions accumulate into the
    stacked leaf's total, matching the vmapped fallback exactly.
    """

    @jax.custom_vjp
    def gnorm(x, scale, tap):
        del tap
        return base_fn(x, scale, *static)

    def fwd(x, scale, tap):
        return base_fn(x, scale, *static), (x, scale)

    def bwd(res, g):
        x, scale = res
        _, vjp = jax.vjp(lambda xx, ss: base_fn(xx, ss, *static), x, scale)
        dx, dscale = vjp(g)
        dtap = jnp.sum(jnp.square(dscale.astype(jnp.float32)))
        return dx, dscale, dtap

    gnorm.defvjp(fwd, bwd)
    return gnorm


# --------------------------------------------------------------------------- #
# per-example quantization (pass 2): vmap-parity semantics on batched tensors
# --------------------------------------------------------------------------- #
def per_example_quantizer(q: Callable) -> Callable:
    """Wrap ``q(v, key)`` so a batched (B, ...) tensor is quantized exactly
    like B vmapped (1, ...) per-example tensors: per-example max scaling,
    and one hoisted uniform draw shared across examples (the key does not
    depend on the lane, so ``vmap`` hoists it — bit-identical to the vmap
    path's draws)."""

    def qpe(v, key):
        return jax.vmap(lambda vi: q(vi[None], key)[0])(v)

    return qpe


# --------------------------------------------------------------------------- #
# param partitioning: hooked (ghost-normed) vs non-hooked (vmapped fallback)
# --------------------------------------------------------------------------- #
def _mask_leaves(params, hooked_mask):
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    m_struct = jax.tree_util.tree_structure(hooked_mask)
    if m_struct != treedef:
        raise ValueError("ghost hooked_mask structure does not match params "
                         f"({m_struct} vs {treedef})")
    m_leaves = [bool(m) for m in jax.tree_util.tree_leaves(hooked_mask)]
    return p_leaves, m_leaves, treedef


def per_example_state_bytes(params, hooked_mask, batch_size: int,
                            itemsize: int = 4, aux: GhostAux = None) -> dict:
    """Analytic estimate of per-example gradient state (the memory term
    that scales with batch size) for the two grad modes.

    vmap materializes every parameter per example; ghost only materializes
    the non-hooked fallback leaves (Gram buffers are O(B * T^2) transients
    and are excluded — see benchmarks/dp_throughput.py).  With a model's
    :class:`GhostAux` the aux-covered leaves count as hooked — for dense
    LMs that drives ``params_nonhooked`` to exactly zero.
    """
    hooked_mask = effective_hooked_mask(params, hooked_mask, aux)
    p_leaves, m_leaves, _ = _mask_leaves(params, hooked_mask)
    total = sum(int(np.prod(l.shape)) for l in p_leaves)
    nonhooked = sum(int(np.prod(l.shape))
                    for l, m in zip(p_leaves, m_leaves) if not m)
    return {
        "params_total": total,
        "params_nonhooked": nonhooked,
        "vmap_bytes": batch_size * total * itemsize,
        "ghost_bytes": batch_size * nonhooked * itemsize,
    }


# --------------------------------------------------------------------------- #
# the two-pass driver
# --------------------------------------------------------------------------- #
def ghost_per_example_norms(loss_fn: Callable, params, batch, *,
                            rng: jax.Array, hooked_mask,
                            aux: Optional[GhostAux] = None,
                            microbatch: int = 0,
                            ) -> Tuple[jax.Array, jax.Array]:
    """Pass 1 alone: ``(per_example_losses, per_example_global_norms)``.

    ``loss_fn(params, example, rng)`` is the per-example loss the vmap path
    consumes; the returned norms match ``vmap(grad)`` global l2 norms (of
    the actually-quantized per-example grads) to fp32 tolerance.

    ``aux`` supplies the model's extra hooks (embedding/head coverage);
    ``microbatch > 0`` scans fixed-size example chunks instead of vmapping
    the whole batch, bounding pass-1 live memory by one chunk of
    activations (numerically identical — examples are independent).
    """
    hooked = effective_hooked_mask(params, hooked_mask, aux)
    p_leaves, m_leaves, treedef = _mask_leaves(params, hooked)
    nonhooked = [l for l, m in zip(p_leaves, m_leaves) if not m]
    norm_scales = aux is not None and aux.hook_norm_scales

    def rebuild(nh):
        it = iter(nh)
        return jax.tree_util.tree_unflatten(
            treedef,
            [l if m else next(it) for l, m in zip(p_leaves, m_leaves)])

    def one_example(ex):
        taps0 = aux.make_taps(ex) if aux is not None else None

        def tapped_loss(args, ex):
            nh, tap, ataps = args
            with norm_pass(tap, norm_scales=norm_scales):
                if aux is None:
                    return loss_fn(rebuild(nh), ex, rng), None
                return aux.tapped_loss(rebuild(nh), ex, rng, ataps)

        (loss, fwd), (g_nh, dtap, dataps) = jax.value_and_grad(
            tapped_loss, has_aux=True)((nonhooked, jnp.float32(0.0), taps0),
                                       ex)
        sq = dtap + sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in g_nh)
        if aux is not None:
            sq = sq + aux.combine(dataps, fwd, ex)
        return loss, sq

    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    if microbatch and 0 < microbatch < n:
        if n % microbatch != 0:
            raise ValueError(f"batch {n} not divisible by "
                             f"ghost_microbatch {microbatch}")
        chunks = jax.tree_util.tree_map(
            lambda x: x.reshape((n // microbatch, microbatch) + x.shape[1:]),
            batch)

        def scan_body(carry, chunk):
            losses, sqs = jax.vmap(one_example)(chunk)
            return carry, (losses, sqs)

        _, (losses, sq_norms) = jax.lax.scan(scan_body, None, chunks)
        losses = losses.reshape(-1)
        sq_norms = sq_norms.reshape(-1)
    else:
        losses, sq_norms = jax.vmap(one_example)(batch)
    return losses, jnp.sqrt(sq_norms)


def _two_pass(loss_fn, per_example_loss_fn, params, batch, *, clip_norm,
              rng, hooked_mask, aux, ghost_microbatch, constrain=None):
    """Shared core of the (un)sharded drivers: pass 1 + reweighted pass 2
    over whatever batch (or local shard) it is handed.  Returns
    ``(grads_f32_tree, losses, norms)``."""
    r = jax.random.fold_in(rng, 0)   # the vmap path's microbatch-0 fold
    losses, norms = scope("ghost_norm_pass", lambda p, b, key: (
        ghost_per_example_norms(loss_fn, p, b, rng=key,
                                hooked_mask=hooked_mask, aux=aux,
                                microbatch=ghost_microbatch)))(
        params, batch, r)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
    scale = jax.lax.stop_gradient(scale)

    pass2_batch = constrain(batch) if constrain is not None else batch

    def weighted_loss(p):
        with grad_pass():
            pel = per_example_loss_fn(p, pass2_batch, r)
        return jnp.vdot(scale, pel.astype(jnp.float32))

    grads = scope("ghost_grad_pass", jax.grad(weighted_loss))(params)
    return grads, losses, norms


def _clip_metrics(losses, norms, clip_norm):
    n = losses.shape[0]
    return {
        "loss": losses.astype(jnp.float32).sum() / n,
        "grad_norm_mean": norms.mean(),
        "grad_norm_max": norms.max(),
        "clip_fraction": (norms > clip_norm).mean(),
    }


def ghost_clipped_grad_sum(
    loss_fn: Callable,
    per_example_loss_fn: Callable,
    params,
    batch,
    *,
    clip_norm: float,
    rng: jax.Array,
    hooked_mask,
    accum_dtype=jnp.float32,
    aux: Optional[GhostAux] = None,
    ghost_microbatch: int = 0,
    constrain: Callable = None,
) -> Tuple[object, dict]:
    """Sum over the batch of per-example clipped gradients, ghost style.

    ``loss_fn(params, example, rng)``: scalar loss of ONE example (the same
    callable the vmap path consumes — used for pass 1).
    ``per_example_loss_fn(params, batch, rng) -> (B,)``: batched per-example
    losses (used for pass 2's single reweighted backward).
    ``hooked_mask``: bool pytree matching ``params`` — True leaves are
    covered by ghost hooks (their norms arrive via the tap), False leaves
    go through the vmapped norm-only fallback.
    ``aux``: the model's :class:`GhostAux` (embedding/head hooks);
    ``ghost_microbatch``: pass-1 chunk size (0 = whole batch);
    ``constrain``: optional sharding constraint applied to the pass-2
    batch (the data-parallel GSPMD formulation).

    Returns ``(grad_sum, metrics)`` with the same metrics contract as
    ``repro.dp.clip.per_example_clipped_grad_sum``.
    """
    grads, losses, norms = _two_pass(
        loss_fn, per_example_loss_fn, params, batch, clip_norm=clip_norm,
        rng=rng, hooked_mask=hooked_mask, aux=aux,
        ghost_microbatch=ghost_microbatch, constrain=constrain)
    grad_sum = jax.tree_util.tree_map(lambda g: g.astype(accum_dtype), grads)
    return grad_sum, _clip_metrics(losses, norms, clip_norm)


def sharded_ghost_clipped_grad_sum(
    loss_fn: Callable,
    per_example_loss_fn: Callable,
    params,
    batch,
    *,
    clip_norm: float,
    rng: jax.Array,
    hooked_mask,
    mesh,
    data_axes: Tuple[str, ...] = ("pod", "data"),
    accum_dtype=jnp.float32,
    aux: Optional[GhostAux] = None,
    ghost_microbatch: int = 0,
) -> Tuple[object, dict]:
    """Data-parallel ghost driver: ``shard_map`` over the mesh's data axes.

    Each shard runs both passes on its local examples (per-shard
    squared-norm taps; the scales a shard's pass 2 needs are exactly its
    local examples'), then the clipped grad sums are combined with ONE
    ``psum`` — no per-microbatch reduction, mirroring ``partial_accum``'s
    communication shape.  Losses/norms are all-gathered (tiled, in shard
    order = batch order) so the metrics contract matches the unsharded
    driver bit-for-bit up to fp32 reduction order.

    Params must be replicated across ``data_axes`` (the standard DP data-
    parallel layout); model-parallel param sharding should use the GSPMD
    formulation (``ghost_clipped_grad_sum`` + batch constraint) instead.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel.axes import partitioning_context

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(a for a in data_axes if sizes.get(a, 1) > 1)
    if not axes:
        return ghost_clipped_grad_sum(
            loss_fn, per_example_loss_fn, params, batch,
            clip_norm=clip_norm, rng=rng, hooked_mask=hooked_mask,
            accum_dtype=accum_dtype, aux=aux,
            ghost_microbatch=ghost_microbatch)
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    shards = int(np.prod([sizes[a] for a in axes]))
    if n % shards != 0:
        raise ValueError(f"global batch {n} not divisible by the "
                         f"{shards}-way data sharding {axes}")

    def body(p, local_batch, r):
        # logical-axis constraints are global-view annotations; inside the
        # manual (per-shard) region they must be inert
        with partitioning_context(None):
            grads, losses, norms = _two_pass(
                loss_fn, per_example_loss_fn, p, local_batch,
                clip_norm=clip_norm, rng=r, hooked_mask=hooked_mask,
                aux=aux, ghost_microbatch=ghost_microbatch)
        grads = jax.lax.psum(grads, axes)          # the one collective
        losses = jax.lax.all_gather(losses, axes, tiled=True)
        norms = jax.lax.all_gather(norms, axes, tiled=True)
        return grads, losses, norms

    # replication checking off: the body (vmapped custom-VJP hooks, scans)
    # is outside what the checker can prove; psum / tiled all_gather make
    # every output replicated
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axes), P()),
        out_specs=(P(), P(), P()), check_vma=False)
    grads, losses, norms = fn(params, batch, rng)
    grad_sum = jax.tree_util.tree_map(lambda g: g.astype(accum_dtype), grads)
    return grad_sum, _clip_metrics(losses, norms, clip_norm)
