"""Dense decoder-only GQA transformer (gemma / yi / stablelm families).

Structure: RMSNorm -> GQA attention (RoPE) -> residual -> RMSNorm -> gated
MLP (GeGLU/SwiGLU) -> residual; tied embeddings by default; layers executed
with ``lax.scan``; DPQuant per-layer flags gate every GEMM through
``repro.quant.fake_quant.qeinsum`` (forward + dgrad + wgrad quantization).

Sharding-driven padding (DESIGN.md §5): query heads are padded up to
``pad_heads_to`` (extra heads zero-initialized); the vocab is padded to
``pad_vocab_to`` (padded logits masked in the loss).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, QuantConfig
from repro.models import common as cm
from repro.models.registry import Model, register_family
from repro.parallel.axes import logical_constraint as lc
from repro.runtime.tracing import scope


# --------------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------------- #
def init_block_stack(key, cfg: ModelConfig, n_layers: int):
    d, hp, kv, hd, f = (cfg.d_model, cfg.padded_heads, cfg.n_kv_heads,
                        cfg.head_dim, cfg.d_ff)
    pdt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    L = n_layers

    def dinit(k, shape, fan_in):
        return cm.dense_init(k, shape, fan_in, pdt)

    wq = dinit(keys[0], (L, d, hp, hd), d)
    if hp != cfg.n_heads:
        # zero the padded query heads so padding is semantics-preserving
        head_mask = (jnp.arange(hp) < cfg.n_heads).astype(pdt)
        wq = wq * head_mask[None, None, :, None]
    blocks = {
        "attn_norm": jnp.zeros((L, d), pdt),
        "wq": wq,
        "wk": dinit(keys[1], (L, d, kv, hd), d),
        "wv": dinit(keys[2], (L, d, kv, hd), d),
        "wo": dinit(keys[3], (L, hp, hd, d), hp * hd),
        "mlp_norm": jnp.zeros((L, d), pdt),
        "wi_gate": dinit(keys[4], (L, d, f), d),
        "wi_up": dinit(keys[5], (L, d, f), d),
        "wo_mlp": dinit(keys[6], (L, f, d), f),
    }
    return blocks


BLOCK_AXES = {
    "attn_norm": ("layers", "embed"),
    "wq": ("layers", "embed", "heads", "head_dim"),
    "wk": ("layers", "embed", "kv_heads", "head_dim"),
    "wv": ("layers", "embed", "kv_heads", "head_dim"),
    "wo": ("layers", "heads", "head_dim", "embed"),
    "mlp_norm": ("layers", "embed"),
    "wi_gate": ("layers", "embed", "mlp"),
    "wi_up": ("layers", "embed", "mlp"),
    "wo_mlp": ("layers", "mlp", "embed"),
}


def init_params(key, cfg: ModelConfig):
    pdt = jnp.dtype(cfg.param_dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params = {
        "embed": cm.embed_init(k_embed, (cfg.padded_vocab, cfg.d_model), pdt),
        "final_norm": jnp.zeros((cfg.d_model,), pdt),
        "blocks": init_block_stack(k_blocks, cfg, cfg.n_layers),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(
            k_head, (cfg.d_model, cfg.padded_vocab), cfg.d_model, pdt)
    return params


def param_axes(cfg: ModelConfig):
    axes = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
        "blocks": dict(BLOCK_AXES),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _activation(gate, up, kind: str):
    if kind == "geglu":
        return jax.nn.gelu(gate) * up
    if kind == "swiglu":
        return jax.nn.silu(gate) * up
    if kind == "gelu":
        return jax.nn.gelu(gate)
    if kind == "relu":
        return jax.nn.relu(gate)
    raise ValueError(kind)


def attention_block(x, blk, flag, seed, positions, cfg: ModelConfig,
                    quant: QuantConfig):
    """Pre-norm GQA attention with RoPE; returns the residual branch."""
    qp = functools.partial(cm.qproj, quant_cfg=quant, flag=flag)
    h = cm.rmsnorm(x, blk["attn_norm"])
    cd = jnp.dtype(cfg.compute_dtype)
    h = h.astype(cd)
    q = qp("bsd,dhk->bshk", h, blk["wq"].astype(cd), seed=seed)
    k = qp("bsd,dhk->bshk", h, blk["wk"].astype(cd), seed=seed + 1)
    v = qp("bsd,dhk->bshk", h, blk["wv"].astype(cd), seed=seed + 2)
    q = lc(q, "batch", "seq", "heads", "head_dim")
    q = cm.rope(q, positions, cfg.rope_theta)
    k = cm.rope(k, positions, cfg.rope_theta)
    n_rep = cfg.padded_heads // cfg.n_kv_heads
    kr, vr = cm.repeat_kv(k, n_rep), cm.repeat_kv(v, n_rep)
    out = cm.chunked_causal_attention(
        q, kr, vr, chunk_q=cfg.attn_chunk_q, causal=True,
        scale=1.0 / math.sqrt(cfg.head_dim))
    out = lc(out, "batch", "seq", "heads", "head_dim")
    res = qp("bshk,hkd->bsd", out, blk["wo"].astype(cd), seed=seed + 3)
    return res, (k, v)  # compact (pre-repeat) KV for cache reuse


def mlp_block(x, blk, flag, seed, cfg: ModelConfig, quant: QuantConfig):
    qp = functools.partial(cm.qproj, quant_cfg=quant, flag=flag)
    cd = jnp.dtype(cfg.compute_dtype)
    h = cm.rmsnorm(x, blk["mlp_norm"]).astype(cd)
    gate = qp("bsd,df->bsf", h, blk["wi_gate"].astype(cd), seed=seed + 4)
    up = qp("bsd,df->bsf", h, blk["wi_up"].astype(cd), seed=seed + 5)
    act = _activation(gate, up, cfg.mlp_activation)
    act = lc(act, "batch", "seq", "mlp")
    return qp("bsf,fd->bsd", act, blk["wo_mlp"].astype(cd), seed=seed + 6)


def transformer_block(x, blk, flag, lidx, positions, cfg, quant):
    seed = lidx.astype(jnp.uint32) * jnp.uint32(97)
    attn_out, _ = attention_block(x, blk, flag, seed, positions, cfg, quant)
    x = lc(x + attn_out, "batch", "seq", "embed")
    x = lc(x + mlp_block(x, blk, flag, seed, cfg, quant),
           "batch", "seq", "embed")
    return x


def run_block_stack(x, blocks, qflags, positions, cfg: ModelConfig,
                    quant: QuantConfig, block_fn=transformer_block):
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    def apply_block(carry, blk, flag, lidx):
        return block_fn(carry, blk, flag, lidx, positions, cfg, quant)

    if cfg.remat:
        apply_block = jax.checkpoint(apply_block)

    def body(carry, xs):
        blk, flag, lidx = xs
        return apply_block(carry, blk, flag, lidx), None

    x, _ = jax.lax.scan(body, x, (blocks, qflags, jnp.arange(L)))
    return x


def forward_hidden(params, tokens, qflags, cfg: ModelConfig,
                   quant: QuantConfig, inputs_embeds: Optional[jax.Array] = None,
                   embed_tap: Optional[jax.Array] = None):
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if cfg.family == "dense_lm":
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)  # gemma-style scaling
    if embed_tap is not None:
        # ghost pass-1 gather hook (repro.dp.ghost.GhostAux): the tap's
        # cotangent is the embedding-output cotangent the scatter-grad
        # would consume; injected post-scaling so the embed grad is
        # sqrt(d_model) * scatter(tokens, cotangent)
        x = x + embed_tap
    if inputs_embeds is not None:
        nv = inputs_embeds.shape[1]
        x = jnp.concatenate([inputs_embeds.astype(cd), x[:, nv:]], axis=1)
    x = lc(x, "batch", "seq", "embed")
    positions = jnp.arange(tokens.shape[1])[None, :]
    x = run_block_stack(x, params["blocks"], qflags, positions, cfg, quant)
    return cm.rmsnorm(x, params["final_norm"])


def lm_loss(params, batch, rng, qflags, cfg: ModelConfig, quant: QuantConfig,
            loss_mask_prefix: int = 0, per_example: bool = False,
            ghost_taps=None):
    del rng
    tokens = batch["tokens"]
    taps = ghost_taps or {}
    h = forward_hidden(params, tokens, qflags, cfg, quant,
                       inputs_embeds=batch.get("vision_embeds"),
                       embed_tap=taps.get("embed_out"))
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    mask = None
    if loss_mask_prefix:
        mask = (jnp.arange(tokens.shape[1] - 1)[None, :]
                >= loss_mask_prefix).astype(jnp.float32) \
            * jnp.ones((tokens.shape[0], 1), jnp.float32)
    out = cm.chunked_lm_loss(h[:, :-1], tokens[:, 1:], head,
                             real_vocab=cfg.vocab_size,
                             ce_chunk=cfg.ce_chunk, mask=mask,
                             per_example=per_example,
                             logits_tap=taps.get("logits"))
    if ghost_taps is not None:
        loss, hc = out
        return loss, {"hidden": hc}
    return out


# Ghost-clipping hooks (repro.dp.ghost): every block projection runs
# through cm.qproj -> qeinsum and therefore carries a ghost norm hook;
# norm scales are tapped by the ghost rmsnorm hook and the embedding /
# LM head by the GhostAux hooks below, so dense_lm pass 1 has NO
# vmapped-fallback leaves (asserted in tests/test_dp_ghost.py).
_GHOST_HOOKED_LEAVES = frozenset(
    ("wq", "wk", "wv", "wo", "wi_gate", "wi_up", "wo_mlp"))


def ghost_mask(params):
    def mark(path, _):
        keys = [p.key for p in path
                if isinstance(p, jax.tree_util.DictKey)]
        return bool(keys) and keys[-1] in _GHOST_HOOKED_LEAVES
    return jax.tree_util.tree_map_with_path(mark, params)


def make_ghost_aux(qflags, cfg: ModelConfig, quant: QuantConfig):
    """Dense-LM :class:`repro.dp.ghost.GhostAux`: gather + LM-head hooks.

    Per example, the embedding leaf's grad is the sum of a gather-scatter
    term and (tied embeddings) a head term landing on the SAME leaf:

        d_gather = s * A^T C      A = onehot(tokens) (T, V), C = gather-out
                                  cotangent (T, d), s = sqrt(d_model)
        d_head   = G^T H          G = logits cotangent (S-1, V_pad),
                                  H = f32 hidden rows (S-1, d)

    so ``||d_gather + d_head||^2`` needs the token-equality-masked Gram of
    the lookup cotangents, the head's mixed ghost norm, and — because
    both are one stacked matrix product ``[A; G]^T [sC; H]`` — the cross
    term ``2 <d_gather, d_head> = 2 sum_{s,t} G[s, tok_t] <sC_t, H_s>``.
    All three are Gram-sized (O(T^2 d + S^2 V)); the (V, d) per-example
    grad is never formed.  Untied heads drop the cross term (different
    leaves) and split the two norms across embed / lm_head.
    """
    from repro.dp.ghost import GhostAux, _matpair_sq_norm

    cd = jnp.dtype(cfg.compute_dtype)
    emb_scale = math.sqrt(cfg.d_model) if cfg.family == "dense_lm" else 1.0

    def make_taps(ex):
        t = ex["tokens"].shape[-1]
        return {
            "embed_out": jnp.zeros((1, t, cfg.d_model), cd),
            "logits": jnp.zeros((1, t - 1, cfg.padded_vocab), jnp.float32),
        }

    def tapped_loss(params, ex, rng, taps):
        b1 = jax.tree_util.tree_map(lambda x: x[None], ex)
        return lm_loss(params, b1, rng, qflags, cfg=cfg, quant=quant,
                       ghost_taps=taps)

    def combine(cots, fwd, ex):
        c = cots["embed_out"][0].astype(jnp.float32) * emb_scale  # (T, d)
        g = cots["logits"][0].astype(jnp.float32)                 # (S-1, Vp)
        h = fwd["hidden"][0].astype(jnp.float32)                  # (S-1, d)
        tok = ex["tokens"]
        eq = (tok[:, None] == tok[None, :]).astype(jnp.float32)
        sq_gather = jnp.vdot(eq, c @ c.T)
        sq_head = _matpair_sq_norm(h, g)
        if not cfg.tie_embeddings:
            return sq_gather + sq_head
        cross = jnp.vdot(jnp.take(g, tok, axis=1), h @ c.T)
        return sq_gather + sq_head + 2.0 * cross

    def covers(params):
        # embed + (untied) lm_head via the taps above; *_norm scale
        # leaves via the ghost rmsnorm hook (hook_norm_scales)
        def mark(path, _):
            keys = [p.key for p in path
                    if isinstance(p, jax.tree_util.DictKey)]
            name = keys[-1] if keys else ""
            return name in ("embed", "lm_head") or name.endswith("norm")
        return jax.tree_util.tree_map_with_path(mark, params)

    return GhostAux(make_taps=make_taps, tapped_loss=tapped_loss,
                    combine=combine, covers=covers, hook_norm_scales=True)


# --------------------------------------------------------------------------- #
# serving: prefill + decode with KV cache
# --------------------------------------------------------------------------- #
def _kv_impls(kv_fmt: str, quant: Optional[QuantConfig]):
    """Resolve the (kv_quant, decode_attn) impls for a cache format.

    Backend selection rides the same knob as the other quant ops
    (``QuantConfig.backend``, overridden by ``REPRO_QUANT_BACKEND``);
    formats a backend lacks fall back to ref explicitly.  Resolution is
    a trace-time (python) lookup: the format is structural (it changes
    the cache pytree), so switching it recompiles by construction, and
    nothing else about the policy is baked in — per-tick values (tokens,
    positions, active mask) stay traced.
    """
    from repro.quant import backend as qbackend

    be = quant.backend if quant is not None else None
    kvq, _ = qbackend.get_kv_quant(kv_fmt, be)
    attn, _ = qbackend.get_decode_attn(kv_fmt, be)
    return kvq, attn


def kv_cache_spec(cfg: ModelConfig, batch: int, seq_len: int,
                  kv_fmt: str = "none"):
    from repro.quant import kv_cache as kvc

    cd = jnp.dtype(cfg.compute_dtype)
    L, kv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    code_dt, code_dim = kvc.code_spec(kv_fmt, hd)
    spec = {
        "k": jax.ShapeDtypeStruct((L, batch, kv, seq_len, code_dim),
                                  code_dt or cd),
        "v": jax.ShapeDtypeStruct((L, batch, kv, seq_len, code_dim),
                                  code_dt or cd),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if kv_fmt != "none":
        sds = jax.ShapeDtypeStruct((L, batch, kv, seq_len), kvc.SCALE_DTYPE)
        spec["k_scale"] = sds
        spec["v_scale"] = sds
    return spec


def kv_cache_axes(cfg: ModelConfig, kv_fmt: str = "none"):
    axes = {
        "k": ("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
        "v": ("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
        "pos": None,
    }
    if kv_fmt != "none":
        axes["k_scale"] = ("layers", "batch", "kv_heads", "kv_seq")
        axes["v_scale"] = ("layers", "batch", "kv_heads", "kv_seq")
    return axes


def prefill(params, batch, cfg: ModelConfig, quant: QuantConfig,
            cache_len: Optional[int] = None, kv_fmt: str = "none",
            prompt_len=None):
    """Run the full prompt; return (last-token logits, filled KV cache).

    ``prompt_len`` (None or a traced int32 scalar) supports bucketed
    prefill: the token batch may be padded beyond the real prompt, and
    the last-token logits / cache position / logits-head key fold are
    taken at ``prompt_len`` instead of the padded length.  Padding is
    semantics-preserving because attention is causal (rows < prompt_len
    never see the pad) and every cache row at index >= pos is masked by
    ``decode_attend`` until a decode tick overwrites it — the same
    contract that already covers stale KV in reused slots.

    ``kv_fmt`` selects the cache storage format: quantized formats write
    the scanned K/V rows through the dispatched ``kv_quant`` op and the
    cache grows per-(token, head) bf16 scale arrays (docs/SERVING.md).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
    if cfg.family == "dense_lm":
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    ve = batch.get("vision_embeds")
    if ve is not None:
        x = jnp.concatenate([ve.astype(cd), x[:, ve.shape[1]:]], axis=1)
    x = lc(x, "batch", "seq", "embed")
    positions = jnp.arange(S)[None, :]
    qflags = jnp.zeros((cfg.n_layers,), jnp.float32)  # serving: no fake-quant

    def body(carry, xs):
        blk, flag, lidx = xs
        seed = lidx.astype(jnp.uint32) * jnp.uint32(97)
        attn_out, (k, v) = attention_block(carry, blk, flag, seed, positions,
                                           cfg, quant)
        x2 = lc(carry + attn_out, "batch", "seq", "embed")
        x2 = lc(x2 + mlp_block(x2, blk, flag, seed, cfg, quant),
                "batch", "seq", "embed")
        kc = jnp.transpose(k, (0, 2, 1, 3))  # (B, KV, S, hd)
        vc = jnp.transpose(v, (0, 2, 1, 3))
        if cache_len > S:
            pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0)]
            kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
        return x2, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["blocks"], qflags, jnp.arange(cfg.n_layers)))
    if prompt_len is None:
        plen = jnp.asarray(S, jnp.int32)
        x_last = x[:, -1]
    else:
        plen = jnp.asarray(prompt_len, jnp.int32)
        x_last = jax.lax.dynamic_slice_in_dim(x, plen - 1, 1, axis=1)[:, 0]
    h_last = cm.rmsnorm(x_last, params["final_norm"]).astype(jnp.float32)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    # even folds = prefill, odd folds = decode (pos==S after prefill, so a
    # bare fold of the position would reuse the first decode step's key)
    logits = cm.qlogits(h_last, head, quant_cfg=quant,
                        key=jax.random.fold_in(jax.random.PRNGKey(17),
                                               2 * plen))
    ks = lc(ks, "layers", "batch", "kv_heads", "kv_seq", "head_dim")
    vs = lc(vs, "layers", "batch", "kv_heads", "kv_seq", "head_dim")
    cache = {"k": ks, "v": vs, "pos": plen}
    if kv_fmt != "none":
        kvq, _ = _kv_impls(kv_fmt, quant)
        kc, ksc = kvq(ks)
        vc, vsc = kvq(vs)
        cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc,
                 "pos": plen}
    return logits, cache


def decode_attend(q, k_cache, v_cache, pos, cfg: ModelConfig):
    """One-token GQA attention against a (B, KV, S, hd) cache.

    ``pos`` is either a scalar (lockstep decode: every row sits at the same
    position) or a (B,) vector of per-slot positions (continuous batching:
    each slot attends to its own prefix only).  Cache entries beyond a row's
    position are masked to exactly zero probability, so a zero-padded cache
    of any length yields bit-identical attention output.

    This is the ``kv_fmt="none"`` case of the dispatched ``decode_attn``
    op; the historical pure-jnp math lives in
    :func:`repro.quant.kv_cache.ref_decode_attn` (bit-for-bit identical)
    and ``_decode_trunk`` routes every format — including ``none`` —
    through the dispatcher.  This thin alias stays for direct callers.
    """
    from repro.quant import kv_cache as kvc
    return kvc.ref_decode_attn("none", q, k_cache, v_cache, None, None, pos,
                               n_kv=cfg.n_kv_heads,
                               scale=1.0 / math.sqrt(cfg.head_dim))


def _decode_trunk(params, cache, token, pos, cfg: ModelConfig,
                  quant: Optional[QuantConfig] = None, kv_fmt: str = "none"):
    """Shared one-token transformer trunk for lockstep and slot decode.

    ``pos`` is a (B,) per-row position vector (lockstep decode broadcasts
    its scalar); each row's KV is written at its own position and attends
    to its own prefix.  Returns the final-norm hidden states (B, d) f32
    and the updated cache arrays (everything but ``pos``) — the
    logits-head key schedule is the one place the two decode modes
    legitimately differ, so it stays with the callers.

    Quantized cache formats write each row through the dispatched
    ``kv_quant`` op (codes + per-(row, head) bf16 scale) and attend
    through the dispatched ``decode_attn`` op, which fuses dequant into
    the QK/PV contractions on the pallas backend.  Write-then-attend
    order is what makes bucketed prefill and slot reuse safe: the row at
    the slot's own position is always fresh before attention reads it,
    and rows beyond ``pos`` are masked.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], token, axis=0).astype(cd)
    if cfg.family == "dense_lm":
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    positions = pos[:, None]                             # (B, 1)
    quantized = kv_fmt != "none"
    kvq, attend = _kv_impls(kv_fmt, quant)
    attn_scale = 1.0 / math.sqrt(cfg.head_dim)

    # per-row cache write: (KV, S, Dc) gets a (KV, 1, Dc) slab at pos_i;
    # scale rows (KV, S) get a (KV, 1) slab
    write = jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p, 0)))
    swrite = jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (0, p)))

    def project_qkv(x, blk):
        h = cm.rmsnorm(x, blk["attn_norm"]).astype(cd)
        q = jnp.einsum("bd,dhk->bhk", h, blk["wq"].astype(cd))
        k = jnp.einsum("bd,dhk->bhk", h, blk["wk"].astype(cd))
        v = jnp.einsum("bd,dhk->bhk", h, blk["wv"].astype(cd))
        q = cm.rope(q[:, None], positions, cfg.rope_theta)[:, 0]
        k = cm.rope(k[:, None], positions, cfg.rope_theta)[:, 0]
        return q, k, v

    def write_kv(k, v, kc, vc, ksc, vsc):
        if quantized:
            k_codes, k_sc = kvq(k)                       # (B, KV, Dc) codes
            v_codes, v_sc = kvq(v)
            kc = write(kc, k_codes[:, :, None, :].astype(kc.dtype), pos)
            vc = write(vc, v_codes[:, :, None, :].astype(vc.dtype), pos)
            ksc = swrite(ksc, k_sc[:, :, None].astype(ksc.dtype), pos)
            vsc = swrite(vsc, v_sc[:, :, None].astype(vsc.dtype), pos)
        else:
            kc = write(kc, k[:, :, None, :].astype(kc.dtype), pos)
            vc = write(vc, v[:, :, None, :].astype(vc.dtype), pos)
        return kc, vc, ksc, vsc

    def attend_cache(q, kc, vc, ksc, vsc):
        return attend(q, kc, vc, ksc, vsc, pos,
                      n_kv=cfg.n_kv_heads, scale=attn_scale)

    def project_out(ctx, wo):
        return jnp.einsum("bhk,hkd->bd", ctx.astype(cd), wo.astype(cd))

    def mlp(x, blk):
        h2 = cm.rmsnorm(x, blk["mlp_norm"]).astype(cd)
        gate = jnp.einsum("bd,df->bf", h2, blk["wi_gate"].astype(cd))
        up = jnp.einsum("bd,df->bf", h2, blk["wi_up"].astype(cd))
        act = _activation(gate, up, cfg.mlp_activation)
        return jnp.einsum("bf,fd->bd", act, blk["wo_mlp"].astype(cd))

    def body(carry, xs):
        if quantized:
            blk, kc, vc, ksc, vsc = xs
        else:
            blk, kc, vc = xs
            ksc = vsc = None
        q, k, v = scope("attn_proj", project_qkv)(carry, blk)
        kc, vc, ksc, vsc = scope("kv_write", write_kv)(k, v, kc, vc, ksc,
                                                        vsc)
        ctx = scope("decode_attn", attend_cache)(q, kc, vc, ksc, vsc)
        x2 = carry + scope("attn_proj", project_out)(ctx, blk["wo"])
        x2 = x2 + scope("mlp", mlp)(x2, blk)
        if quantized:
            return x2, (kc, vc, ksc, vsc)
        return x2, (kc, vc)

    if quantized:
        xs = (params["blocks"], cache["k"], cache["v"],
              cache["k_scale"], cache["v_scale"])
        x, (ks, vs, kss, vss) = jax.lax.scan(body, x, xs)
        upd = {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss}
    else:
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["blocks"], cache["k"], cache["v"]))
        upd = {"k": ks, "v": vs}
    return cm.rmsnorm(x, params["final_norm"]).astype(jnp.float32), upd


def decode_step(params, cache, token, cfg: ModelConfig, quant: QuantConfig,
                kv_fmt: str = "none"):
    """Append one token; returns (logits, new cache)."""
    B = token.shape[0]
    pos = cache["pos"]
    h_last, upd = _decode_trunk(params, cache, token,
                                jnp.full((B,), pos, jnp.int32), cfg,
                                quant=quant, kv_fmt=kv_fmt)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T
    logits = cm.qlogits(h_last, head, quant_cfg=quant,
                        key=jax.random.fold_in(jax.random.PRNGKey(17),
                                               2 * pos + 1))
    new_cache = dict(upd, pos=pos + 1)
    return logits, new_cache


# --------------------------------------------------------------------------- #
# continuous batching: slot-pool cache + fused masked decode
# --------------------------------------------------------------------------- #
def slot_cache_spec(cfg: ModelConfig, n_slots: int, max_seq: int,
                    kv_fmt: str = "none"):
    """Slot-pool KV cache: like ``kv_cache_spec`` but with per-slot positions.

    The batch axis indexes *slots* (not requests); ``pos`` is a (n_slots,)
    vector so every slot tracks its own sequence length, which is what lets
    requests of different lengths share one fused decode step.  Quantized
    ``kv_fmt`` values swap the K/V arrays for code arrays and add
    per-(slot, token, kv-head) bf16 scale arrays, exactly as in
    ``kv_cache_spec``.
    """
    spec = kv_cache_spec(cfg, n_slots, max_seq, kv_fmt=kv_fmt)
    spec["pos"] = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    return spec


def decode_slots(params, cache, tokens, active, cfg: ModelConfig,
                 quant: QuantConfig, kv_fmt: str = "none"):
    """One fused decode tick across all slots at per-slot positions.

    ``tokens``: (K,) int32 last token of each slot; ``active``: (K,) bool —
    only active slots advance their position (inactive rows still flow
    through the batched GEMMs, but their cache writes land at a stale
    position that is either masked by ``decode_attend`` or overwritten by
    the next admission's prefill, so they cannot perturb live slots).

    For a slot at position ``p`` this computes exactly what ``decode_step``
    computes for a row of a lockstep batch at ``pos == p`` (they share
    ``_decode_trunk``); the quantized-logits key ``fold_in(PRNGKey(17),
    2p + 1)`` is evaluated per slot on its own (1, d) hidden row so the
    draw is bit-identical to the oneshot driver's.
    """
    pos = cache["pos"]                                   # (K,)
    h_last, upd = _decode_trunk(params, cache, tokens, pos, cfg,
                                quant=quant, kv_fmt=kv_fmt)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"].T

    def lm_head(h_last, head, pos):
        if quant is None or quant.fmt == "none":
            return cm.qlogits(h_last, head, quant_cfg=quant,
                              key=jax.random.PRNGKey(0))   # key unused
        # per-slot quantized logits: each slot's (1, d) row goes through
        # the dispatcher with its own position-derived key, matching the
        # oneshot decode_step draw for that position bit-for-bit; vmap
        # batches the K rows into one dispatch with identical bits
        keys = jax.vmap(lambda p: jax.random.fold_in(
            jax.random.PRNGKey(17), 2 * p + 1))(pos)
        return jax.vmap(
            lambda hrow, k: cm.qlogits(hrow[None], head, quant_cfg=quant,
                                       key=k)[0])(h_last, keys)

    logits = scope("lm_head", lm_head)(h_last, head, pos)
    new_cache = dict(upd, pos=pos + active.astype(jnp.int32))
    return logits, new_cache


# --------------------------------------------------------------------------- #
# registry glue
# --------------------------------------------------------------------------- #
def _dense_batch_spec(cfg: ModelConfig):
    def spec(batch: int, seq: int):
        return {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    return spec


def _dense_batch_axes(cfg: ModelConfig):
    def axes():
        return {"tokens": ("batch", "seq")}
    return axes


@register_family("dense_lm")
def build_dense_lm(cfg: ModelConfig, quant: QuantConfig) -> Model:
    return Model(
        config=cfg, quant=quant,
        init=functools.partial(init_params, cfg=cfg),
        param_axes=lambda: param_axes(cfg),
        loss_fn=functools.partial(lm_loss, cfg=cfg, quant=quant),
        batch_spec=_dense_batch_spec(cfg),
        batch_axes=_dense_batch_axes(cfg),
        prefill=functools.partial(prefill, cfg=cfg, quant=quant),
        decode_step=functools.partial(decode_step, cfg=cfg, quant=quant),
        cache_spec=functools.partial(kv_cache_spec, cfg),
        cache_axes=lambda **kw: kv_cache_axes(cfg, **kw),
        decode_slots=functools.partial(decode_slots, cfg=cfg, quant=quant),
        slot_cache_spec=functools.partial(slot_cache_spec, cfg),
        kv_formats=("none", "int8", "luq_fp4"),
        per_example_loss=functools.partial(lm_loss, cfg=cfg, quant=quant,
                                           per_example=True),
        ghost_mask=ghost_mask,
        ghost_aux=functools.partial(make_ghost_aux, cfg=cfg, quant=quant),
    )
