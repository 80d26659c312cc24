"""Plain reference for DP-SGD training of the CIFAR/GTSRB ResNet.

Straight ``jax.numpy``: one example at a time through the forward pass and
``jax.grad``, each example's gradient clipped to ``clip`` and summed,
Gaussian noise added to the sum, the sum divided by the batch size, one
SGD step.  Float32 under ``default_matmul_precision("highest")``; the
control holds and computes everything in bfloat16 instead (parameters,
activations, gradients, their sum, the noise and the update).

The layers the cell quantizes run LUQ-FP4 (Chmiel et al., 2024) on the
inputs of all three GEMMs of a conv (forward, input gradient, weight
gradient), each example's activations and cotangents scaled by their own
maximum.  Shared randomness is the only thing taken from the system's
conventions, so that the stochastic roundings and the noise of the two
sides are the same draws: the key of a conv's quantizer is
``fold_in(fold_in(PRNGKey(0), 11 * layer + conv), operand)``, its uniforms
are drawn over a ``(rows padded to 256, 256)`` view of the tensor, a
step's key is ``PRNGKey(step seed)`` split into (clip, noise, loss), and
the noise of each parameter leaf comes from one split of the noise key in
the leaves' flattened order.  Weights are made here from the seed by the
published He initialisation with the same key schedule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import leaf_norms

LUQ_LEVELS = 7          # 3 exponent bits: {0} and 2^-k, k = 0..6
GN_GROUPS = 8


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def _conv_init(key, shape):
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init(key, m: dict):
    widths = m["widths"]
    params = {"stem": {"conv": _conv_init(key, (3, 3, m["in_channels"],
                                                widths[0])),
                       "gn": _gn(widths[0])}}
    keys = jax.random.split(key, 64)
    ki, in_c, stages = 1, widths[0], []
    for si, (n, w) in enumerate(zip(m["resnet_blocks"], widths)):
        stage = []
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"conv1": _conv_init(keys[ki], (3, 3, in_c, w))}
            ki += 1
            blk["gn1"] = _gn(w)
            blk["conv2"] = _conv_init(keys[ki], (3, 3, w, w))
            ki += 1
            blk["gn2"] = _gn(w)
            if stride != 1 or in_c != w:
                blk["proj"] = _conv_init(keys[ki], (1, 1, in_c, w))
                ki += 1
                blk["proj_gn"] = _gn(w)
            stage.append(blk)
            in_c = w
            if ki >= 60:
                keys = jax.random.split(keys[-1], 64)
                ki = 0
        stages.append(stage)
    params["stages"] = stages
    params["head"] = {
        "w": jax.random.normal(keys[ki], (in_c, m["num_classes"]),
                               jnp.float32) / math.sqrt(in_c),
        "b": jnp.zeros((m["num_classes"],), jnp.float32)}
    return params


# ---------------------------------------------------------------------- #
# LUQ-FP4
# ---------------------------------------------------------------------- #
def _uniforms(key, shape):
    n = int(np.prod(shape))
    rows = -(-n // 256)
    rows += (-rows) % 256
    u = jax.random.uniform(key, (rows, 256), jnp.float32)
    return u.reshape(-1)[:n].reshape(shape)


def luq(x, u):
    """Stochastic LUQ-FP4 of a whole tensor with uniforms ``u``, scaled by
    its max |x|."""
    xf = x.astype(jnp.float32)
    alpha = jnp.max(jnp.abs(xf))
    safe = jnp.where(alpha > 0, alpha, 1.0)
    y = jnp.abs(xf) / safe
    lo_level = 2.0 ** (-(LUQ_LEVELS - 1))
    under = jnp.where(u < y / lo_level, lo_level, 0.0)
    k = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(y, lo_level))),
                 -(LUQ_LEVELS - 1), 0.0)
    low = jnp.exp2(k)
    high = jnp.minimum(jnp.exp2(k + 1.0), 1.0)
    up = (y - low) / jnp.maximum(high - low, 1e-30)
    q = jnp.where(y < lo_level, under, jnp.where(u < up, high, low))
    return jnp.where(alpha > 0, jnp.sign(xf) * q * safe, 0.0).astype(x.dtype)


def _qkey(seed: int, fold: int):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                 seed), fold)


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv_plan(params, m: dict) -> dict:
    """Every conv in forward order: ``seed -> (policy layer, weight path,
    stride, one example's input shape, its output shape)``."""
    s, c0 = m["image_size"], m["widths"][0]
    plan = {0: (0, ("stem", "conv"), 1, (1, s, s, m["in_channels"]),
                (1, s, s, c0))}
    li, in_c, hw = 1, c0, s
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            w = blk["conv1"].shape[-1]
            out, sd = -(-hw // stride), 11 * li
            plan[sd] = (li, ("stages", si, bi, "conv1"), stride,
                        (1, hw, hw, in_c), (1, out, out, w))
            plan[sd + 1] = (li, ("stages", si, bi, "conv2"), 1,
                            (1, out, out, w), (1, out, out, w))
            if "proj" in blk:
                plan[sd + 3] = (li, ("stages", si, bi, "proj"), stride,
                                (1, hw, hw, in_c), (1, out, out, w))
            in_c, hw, li = w, out, li + 1
    return plan


def draws(params, m: dict, flags) -> dict:
    """For each quantized conv: its weight quantized for the forward
    (operand 1) and the input gradient (operand 2), and the uniforms of
    one example's input (operands 0, 4) and output cotangent (3, 5).  The
    keys do not depend on the example, so these are made once per step,
    outside the loop over examples."""
    out = {}
    for seed, (li, path, _, xs, gs) in conv_plan(params, m).items():
        if not flags[li]:
            continue
        w = params
        for k in path:
            w = w[k]
        out[seed] = (luq(w, _uniforms(_qkey(seed, 1), w.shape)),
                     luq(w, _uniforms(_qkey(seed, 2), w.shape)),
                     _uniforms(_qkey(seed, 0), xs),
                     _uniforms(_qkey(seed, 3), gs),
                     _uniforms(_qkey(seed, 4), xs),
                     _uniforms(_qkey(seed, 5), gs))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qconv(x, w, stride, d):
    wq1, _, u0, _, _, _ = d
    return _conv(luq(x, u0), wq1.astype(x.dtype), stride)


def _qconv_fwd(x, w, stride, d):
    return _qconv(x, w, stride, d), (x, w, d)


def _qconv_bwd(stride, res, g):
    x, w, d = res
    _, wq2, _, u3, u4, u5 = d
    (dx,) = jax.linear_transpose(
        lambda t: _conv(t, wq2.astype(x.dtype), stride), x)(luq(g, u3))
    xq = luq(x, u4)
    (dw,) = jax.linear_transpose(lambda t: _conv(xq, t, stride), w)(
        luq(g, u5))
    return dx, dw, jax.tree_util.tree_map(jnp.zeros_like, d)


_qconv.defvjp(_qconv_fwd, _qconv_bwd)


def qconv(x, w, seed: int, stride: int, d: dict):
    """Conv whose three GEMMs take LUQ-FP4 inputs when the conv has draws
    (its policy layer is quantized)."""
    if seed not in d:
        return _conv(x, w, stride)
    return _qconv(x, w, stride, d[seed])


# ---------------------------------------------------------------------- #
# model
# ---------------------------------------------------------------------- #
def groupnorm(x, p, eps=1e-5):
    b, h, w, c = x.shape
    g = math.gcd(GN_GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def forward(params, x, d: dict):
    """Logits of one example ``x`` (1, s, s, c); ``d`` holds the draws of
    the quantized convs (see ``draws``)."""
    x = qconv(x, params["stem"]["conv"], 0, 1, d)
    x = jax.nn.relu(groupnorm(x, params["stem"]["gn"]))
    li = 1
    for si, stage in enumerate(params["stages"]):
        for bi, blk in enumerate(stage):
            stride = 2 if (si > 0 and bi == 0) else 1
            sd = 11 * li
            h = jax.nn.relu(groupnorm(qconv(x, blk["conv1"], sd, stride, d),
                                      blk["gn1"]))
            h = groupnorm(qconv(h, blk["conv2"], sd + 1, 1, d), blk["gn2"])
            sc = x
            if "proj" in blk:
                sc = groupnorm(qconv(x, blk["proj"], sd + 3, stride, d),
                               blk["proj_gn"])
            x = jax.nn.relu(h + sc)
            li += 1
    x = x.mean(axis=(1, 2))
    return x @ params["head"]["w"] + params["head"]["b"]


def example_loss(params, x, y, d):
    logits = forward(params, x[None], d).astype(jnp.float32)[0]
    return jax.nn.logsumexp(logits) - logits[y]


# ---------------------------------------------------------------------- #
# DP-SGD
# ---------------------------------------------------------------------- #
# The reference runs three steps and then is done: compile it with the
# least optimisation effort, which halves its compile time on the TPU.
@functools.partial(jax.jit, static_argnames=("flags", "m_items", "clip",
                                             "noise", "lr", "dtype"),
                   compiler_options={"exec_time_optimization_effort": -1.0})
def _step(params, images, labels, seed, *, flags, m_items, clip, noise, lr,
          dtype):
    """One DP-SGD step with every value held and computed in ``dtype``
    (the parameters, activations, gradients, their sum, the noise and the
    update); the loss is reported in float32."""
    m = dict(m_items)
    d = draws(params, m, flags)

    def body(acc, ex):
        x, y = ex
        loss, g = jax.value_and_grad(example_loss)(params, x.astype(dtype),
                                                   y, d)
        sq = sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                 for v in jax.tree_util.tree_leaves(g))
        s = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12))
        acc = jax.tree_util.tree_map(
            lambda a, v: a + (s * v).astype(dtype), acc, g)
        return acc, loss.astype(jnp.float32)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    total, losses = jax.lax.scan(body, zeros, (images, labels))
    _, noise_key, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    leaves, treedef = jax.tree_util.tree_flatten(total)
    keys = jax.random.split(noise_key, len(leaves))
    batch = images.shape[0]
    grads = jax.tree_util.tree_unflatten(treedef, [
        (v + (noise * clip * jax.random.normal(k, v.shape, jnp.float32)
              ).astype(dtype)) / batch for v, k in zip(leaves, keys)])
    new = jax.tree_util.tree_map(lambda p, g: p + (-lr * g).astype(dtype),
                                 params, grads)
    return new, grads, losses.mean()


class DPSteps:
    """The reference's DP-SGD step compiled ahead of time for one policy
    (``flags``: which policy layers are quantized), batch size and dtype,
    so that the compile can overlap other work; ``run`` follows the
    checked steps."""

    def __init__(self, m: dict, flags, batch: int, *, lr: float,
                 clip: float, noise: float, dtype=jnp.float32):
        self.m, self.dtype = m, dtype
        self._init = jax.jit(functools.partial(init, m=m))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype),
            jax.eval_shape(self._init, key))
        s = m["image_size"]
        args = (params,
                jax.ShapeDtypeStruct((batch, s, s, m["in_channels"]),
                                     jnp.float32),
                jax.ShapeDtypeStruct((batch,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.uint32))
        m_items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                               for k, v in m.items()))
        prec = "highest" if dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(prec):
            self._step = _step.lower(
                *args, flags=tuple(int(f) for f in np.asarray(flags)),
                m_items=m_items, clip=clip, noise=noise, lr=lr,
                dtype=dtype).compile()

    def run(self, init_seed: int, images, labels, seeds) -> dict:
        """Follow the checked steps: ``images`` (steps, B, s, s, c),
        ``labels`` (steps, B), ``seeds`` (steps,).  Returns each step's
        mean loss, the per-leaf norms of the first step's noisy gradient,
        and those of the parameters' change over all the steps."""
        params = jax.tree_util.tree_map(
            lambda p: p.astype(self.dtype),
            self._init(jax.random.PRNGKey(init_seed)))
        p0 = params
        losses, grad1 = [], None
        for t in range(len(seeds)):
            params, grads, loss = self._step(
                params, jnp.asarray(images[t]), jnp.asarray(labels[t]),
                jnp.uint32(seeds[t]))
            losses.append(float(loss))
            if grad1 is None:
                grad1 = leaf_norms(grads)
        change = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, p0))
        return {"losses": losses, "grad1": grad1, "change": change}


def dp_steps(m: dict, init_seed: int, images, labels, seeds, flags, *,
             lr: float, clip: float, noise: float,
             dtype=jnp.float32) -> dict:
    """``DPSteps(...).run(...)`` in one call."""
    return DPSteps(m, flags, images.shape[1], lr=lr, clip=clip, noise=noise,
                   dtype=dtype).run(init_seed, images, labels, seeds)
