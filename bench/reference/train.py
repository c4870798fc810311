"""Plain float32 reference of A2Q training, for the training cells.

The forward pass is a Llama-architecture decoder whose every linear is
quantized as the paper prescribes (Colbert et al., arXiv 2308.13504):
weights through the l1-normalized reparameterization ``(v, t, d)`` with the
norm capped by the accumulator width and rounded toward zero (Eq. 17-23),
inputs through a per-tensor learned-scale quantizer, both with
straight-through rounding.  The loss is the mean cross-entropy, a z-loss of
``1e-4 * mean(logsumexp^2)``, and ``reg_lambda`` times the A2Q penalty
``sum max(t - T, 0)`` (paper Sec. 4.1).  The optimizer is AdamW after
clipping the gradients to a global norm, with a linear warm-up and cosine
decay of the learning rate.

It runs in float32 at the highest matmul precision, over blocks of rows
whose gradients it averages, recomputing each layer in the backward pass.
``dot_dtype`` rounds every matmul operand to a narrower type first; the
benchmark's control uses it.  It imports nothing of the program under test.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _ste(x, rounded):
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(a, b, dot_dtype):
    if dot_dtype is not None:
        a = a.astype(dot_dtype).astype(jnp.float32)
        b = b.astype(dot_dtype).astype(jnp.float32)
    return a @ b


def _cap(d, q):
    return 1.0 + math.log2(2.0 ** (q["acc_bits"] - 1) - 1.0) + d - q["act_bits"]


def a2q_weight(leaf, q):
    """Eq. 20: ``clip(rtz(g/s * v / ||v||_1)) * s`` with ``g = 2^min(t, T)``."""
    v, t, d = leaf["v"], leaf["t"], leaf["d"]
    g_over_s = jnp.exp2(jnp.minimum(t, _cap(d, q)) - d)
    l1 = jnp.maximum(jnp.sum(jnp.abs(v), axis=0), 1e-12)
    w = g_over_s * v / l1
    lim = 2 ** (q["weight_bits"] - 1)
    return jnp.clip(_ste(w, jnp.trunc(w)), -lim, lim - 1) * jnp.exp2(d)


def act_quant(x, log2_scale, bits):
    s = jnp.exp2(log2_scale)
    lim = 2 ** (bits - 1)
    return jnp.clip(_ste(x / s, jnp.round(x / s)), -lim, lim - 1) * s


def _linear(leaf, x, q, dot_dtype):
    return _mm(act_quant(x, leaf["aq"]["log2_scale"], q["act_bits"]), a2q_weight(leaf, q), dot_dtype)


def _rms(x, scale, eps):
    return x * (jnp.mean(x * x, axis=-1, keepdims=True) + eps) ** -0.5 * scale


def _rope(x, pos, theta):
    B, T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr = x.reshape(B, T, H, half, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1).reshape(B, T, H, D)


def _layer(x, lp, cfg, dot_dtype):
    q = cfg["a2q"]
    B, T, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    pos = jnp.arange(T)
    a, m = lp["attn"], lp["mlp"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    qh = _rope(_linear(a["wq"], h, q, dot_dtype).reshape(B, T, H, D), pos, theta)
    kh = _rope(_linear(a["wk"], h, q, dot_dtype).reshape(B, T, KV, D), pos, theta)
    vh = _linear(a["wv"], h, q, dot_dtype).reshape(B, T, KV, D)
    G = H // KV
    kh, vh = jnp.repeat(kh, G, axis=2), jnp.repeat(vh, G, axis=2)
    if dot_dtype is not None:
        qh, kh, vh = (t.astype(dot_dtype).astype(jnp.float32) for t in (qh, kh, vh))
    s = jnp.einsum("bthd,bshd->bhts", qh, kh) * D ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if dot_dtype is not None:
        p = p.astype(dot_dtype).astype(jnp.float32)
    o = jnp.einsum("bhts,bshd->bthd", p, vh).reshape(B, T, H * D)
    x = x + _linear(a["wo"], o, q, dot_dtype)
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = jax.nn.silu(_linear(m["w_gate"], h, q, dot_dtype)) * _linear(m["w_in"], h, q, dot_dtype)
    return x + _linear(m["w_out"], g, q, dot_dtype)


def _penalty(params, q):
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda n: isinstance(n, dict) and "v" in n)[0]:
        if isinstance(leaf, dict) and "v" in leaf:
            total = total + jnp.sum(jnp.maximum(leaf["t"] - _cap(leaf["d"], q), 0.0))
    return total


def loss(params, cfg, tokens, targets, dot_dtype=None):
    """Mean cross-entropy + z-loss + ``reg_lambda`` x the A2Q penalty."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    body = jax.checkpoint(lambda x, lp: (_layer(x, lp, cfg, dot_dtype), None))
    x, _ = jax.lax.scan(body, x, params["stacks"]["0"])
    h = _rms(x, params["final_norm"]["scale"], float(cfg["rms_norm_eps"]))
    if "head" in params:
        logits = _linear(params["head"], h, cfg["a2q"], dot_dtype)
    else:
        logits = _mm(h, params["embed"]["table"].T, dot_dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (jnp.mean(lse - gold) + 1e-4 * jnp.mean(lse * lse)
            + cfg["a2q"]["reg_lambda"] * _penalty(params, cfg["a2q"]))


def grads(params, cfg, tokens, targets, rows_per_block: int, dot_dtype=None):
    """Loss and gradient over the batch, a block of rows at a time (blocks of
    equal size, so the mean of the blocks is the mean of the batch)."""
    B = tokens.shape[0]
    nb = B // rows_per_block
    tb = tokens.reshape(nb, rows_per_block, -1)
    gb = targets.reshape(nb, rows_per_block, -1)
    vg = jax.value_and_grad(lambda p, t, g: loss(p, cfg, t, g, dot_dtype))

    def body(acc, blk):
        l, g = vg(params, *blk)
        return jax.tree.map(jnp.add, acc, (l, g)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(body, zero, (tb, gb))
    return l / nb, jax.tree.map(lambda x: x / nb, g)


def lr_at(step, t: dict):
    """Linear warm-up to ``lr`` over ``warmup`` steps, cosine decay to 0 at ``total``."""
    s = jnp.asarray(step, jnp.float32)
    warm = t["lr"] * s / max(t["warmup"], 1)
    prog = jnp.clip((s - t["warmup"]) / max(t["total_steps"] - t["warmup"], 1), 0.0, 1.0)
    return jnp.where(s < t["warmup"], warm, 0.5 * t["lr"] * (1 + jnp.cos(jnp.pi * prog)))


def step(state, batch, cfg, *, rows_per_block: int, dot_dtype=None):
    """One training step on ``state = {"params", "opt_state": {"m", "v",
    "count"}, "step"}``; returns the new state, the loss and the clipped
    gradients the optimizer was given."""
    t = cfg["train"]
    params, opt = state["params"], state["opt_state"]
    l, g = grads(params, cfg, batch["tokens"], batch["targets"], rows_per_block, dot_dtype)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(norm, 1e-9)), g)
    b1, b2, eps = t["b1"], t["b2"], t["eps"]
    c = opt["count"] + 1
    bc1 = 1 - b1 ** c.astype(jnp.float32)
    bc2 = 1 - b2 ** c.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], g)
    lr = lr_at(state["step"], t)
    new = jax.tree.map(lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)),
                       params, m, v)
    return ({"params": new, "opt_state": {"m": m, "v": v, "count": c}, "step": state["step"] + 1},
            l, g)


def init_state(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "opt_state": {"m": z, "v": jax.tree.map(jnp.zeros_like, params),
                                            "count": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32)}
