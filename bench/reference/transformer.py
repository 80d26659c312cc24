"""Plain reference for the dense decoder the serving cells run.

A straight ``jax.numpy`` forward pass over a whole sequence, in float32
under ``default_matmul_precision("highest")``: embedding scaled by
sqrt(d_model); per layer RMSNorm (scale ``1 + w``), multi-head attention
with rotary embeddings (half-split) and a causal softmax, RMSNorm, a
SwiGLU MLP; a final RMSNorm and an untied output head.  No cache, no
kernels, no batching.  The weights are made here from the seed with the
same key schedule as the system's initialiser, rounded to the parameter
dtype the configuration states, and computed with in float32.

The control computes the same forward with every matmul operand rounded
to float8 e4m3 under a per-tensor scale (the precision below the
configuration's bfloat16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0


def init(key, m: dict):
    pdt = jnp.dtype(m["param_dtype"])
    d, h, kv, hd, f, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"], m["n_layers"])
    V = m["vocab_padded"]

    def dense(k, shape, fan_in):
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(pdt)

    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, 8)
    return {
        "embed": (jax.random.normal(k_embed, (V, d), jnp.float32)
                  * 0.02).astype(pdt),
        "final_norm": jnp.zeros((d,), pdt),
        "blocks": {
            "attn_norm": jnp.zeros((L, d), pdt),
            "wq": dense(keys[0], (L, d, h, hd), d),
            "wk": dense(keys[1], (L, d, kv, hd), d),
            "wv": dense(keys[2], (L, d, kv, hd), d),
            "wo": dense(keys[3], (L, h, hd, d), h * hd),
            "mlp_norm": jnp.zeros((L, d), pdt),
            "wi_gate": dense(keys[4], (L, d, f), d),
            "wi_up": dense(keys[5], (L, d, f), d),
            "wo_mlp": dense(keys[6], (L, f, d), f),
        },
        "lm_head": dense(k_head, (d, V), d),
    }


def _f8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    s, _, d = x.shape
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ein(spec, a, b, control):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if control:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b)


# The reference is compiled for a few calls: least optimisation effort.
_FAST_COMPILE = {"exec_time_optimization_effort": -1.0}


@functools.partial(jax.jit, static_argnames=("m_items", "control"),
                   compiler_options=_FAST_COMPILE)
def _layer(x, blk, *, m_items, control):
    """One decoder layer over the whole sequence ``x`` (S, d)."""
    m = dict(m_items)
    ein = functools.partial(_ein, control=control)
    eps, theta = m["norm_eps"], m["rope_theta"]
    s = x.shape[0]
    rep = m["n_heads"] // m["n_kv_heads"]
    with jax.default_matmul_precision("highest"):
        h = _rmsnorm(x, blk["attn_norm"], eps)
        q = _rope(ein("sd,dhk->shk", h, blk["wq"]), theta)
        k = _rope(ein("sd,dhk->shk", h, blk["wk"]), theta)
        v = ein("sd,dhk->shk", h, blk["wv"])
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        sc = ein("qhk,thk->hqt", q, k) / math.sqrt(m["head_dim"])
        mask = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        o = ein("hqt,thk->qhk", p, v)
        x = x + ein("shk,hkd->sd", o, blk["wo"])
        h2 = _rmsnorm(x, blk["mlp_norm"], eps)
        a = jax.nn.silu(ein("sd,df->sf", h2, blk["wi_gate"])) * ein(
            "sd,df->sf", h2, blk["wi_up"])
        return x + ein("sf,fd->sd", a, blk["wo_mlp"])


@functools.partial(jax.jit, static_argnames=("d_model",))
def _embed(embed, tokens, *, d_model):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32) * math.sqrt(
        d_model)


@functools.partial(jax.jit, static_argnames=("eps", "control"),
                   compiler_options=_FAST_COMPILE)
def _logits(x, final_norm, head, *, eps, control):
    with jax.default_matmul_precision("highest"):
        return _ein("sd,dv->sv", _rmsnorm(x, final_norm, eps), head, control)


@jax.jit
def _gaps(ref, pick, targets, start, n):
    """``best - logit[target]`` of each served token's row of ``ref``;
    the target is ``pick``'s first choice when ``pick`` is given."""
    idx = jnp.minimum(start + jnp.arange(targets.shape[0]), ref.shape[0] - 1)
    rows = jnp.take(ref, idx, axis=0)
    if pick is not None:
        targets = jnp.argmax(jnp.take(pick, idx, axis=0), axis=-1)
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, targets[:, None], axis=-1)[:, 0]
    return jnp.where(jnp.arange(targets.shape[0]) < n, best - got, 0.0)


class Reference:
    """The reference's weights from the seed, and the gaps it reads.  The
    forward pass runs layer by layer, one compiled program per layer kind,
    so that only one layer's float32 weights exist at a time."""

    def __init__(self, m: dict, init_seed: int):
        self.m = m
        self.m_items = tuple(sorted(m.items()))
        self.params = jax.jit(functools.partial(init, m=m))(
            jax.random.PRNGKey(init_seed))

    def logits(self, tokens, control: bool = False):
        """Logits (S, V) of one token sequence (S,)."""
        p, m = self.params, self.m
        x = _embed(p["embed"], tokens, d_model=m["d_model"])
        for layer in range(m["n_layers"]):
            blk = {k: v[layer] for k, v in p["blocks"].items()}
            x = _layer(x, blk, m_items=self.m_items, control=control)
        return _logits(x, p["final_norm"], p["lm_head"], eps=m["norm_eps"],
                       control=control)

    def gaps(self, prompt, served, max_seq: int, control: bool = False):
        """For each served token, the reference's best logit minus the
        reference logit of that token (of the control's first choice with
        ``control``), at its position after the prompt and the tokens
        served before it."""
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        n = served.size
        tokens = np.zeros((max_seq,), np.int32)
        tokens[:seq.size] = seq
        targets = np.zeros((max_seq,), np.int32)
        targets[:n] = served
        tokens = jnp.asarray(tokens)
        ref = self.logits(tokens)
        pick = self.logits(tokens, control=True) if control else None
        out = _gaps(ref, pick, jnp.asarray(targets),
                    jnp.int32(prompt.size - 1), jnp.int32(n))
        return np.asarray(out)[:n]

    def close(self):
        self.params = None
