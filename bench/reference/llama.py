"""Plain float32 reference of the served model, for the serving cells.

A Llama-architecture decoder (RMSNorm, grouped-query attention with RoPE,
SwiGLU) with the A2Q quantizers of Colbert et al. (arXiv 2308.13504): every
linear's input is rounded to ``act_bits``-bit signed codes at its learned
per-tensor scale, and its weights are the deployed integer codes times their
per-channel scale.  It runs the whole sequence at once with no cache, in
float32 at the highest matmul precision, a layer at a time, so that it fits
beside nothing else on the chip.  It imports nothing of the program under
test.

Departures from the published model, each stated by the configuration
file: RoPE rotates adjacent pairs ``(x[2i], x[2i+1])`` (the weights are
random, so the pairing is a fixed permutation of the head dimension), and
with ``kv_bits`` every key and value is attended as ``kv_bits``-bit codes
with one scale per token and KV head (its absolute maximum over the head
dimension over ``2^(kv_bits-1) - 1``), as the cache keeps it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_Q_BLOCK = 256


def _act(x, log2_scale, bits: int):
    s = jnp.exp2(log2_scale)
    lim = 2 ** (bits - 1)
    return jnp.clip(jnp.round(x / s), -lim, lim - 1) * s


def _linear(leaf, x, bits: int, i=None):
    q8, s8, a = leaf["q8"], leaf["s8"], leaf["aq"]["log2_scale"]
    if i is not None:
        q8, s8, a = (jax.lax.dynamic_index_in_dim(t, i, keepdims=False) for t in (q8, s8, a))
    w = q8.astype(jnp.float32) * s8[None, :]
    return _act(x, a, bits) @ w


def _kv(x, bits):
    """Keys or values ``(T, KV, D)`` as the cache keeps them."""
    if not bits:
        return x
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), jnp.finfo(jnp.float32).tiny) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x.reshape(T, H, half, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1).reshape(T, H, D)


def _attention(q, k, v):
    """Causal attention of ``q (T, H, D)`` over ``k, v (T, KV, D)``, query
    blocks at a time."""
    T, H, D = q.shape
    KV = k.shape[1]
    G = H // KV
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    kpos = jnp.arange(T)

    def block(args):
        qb, qpos = args
        s = jnp.einsum("thd,shd->ths", qb, kk) * (D ** -0.5)
        s = jnp.where((kpos[None, None, :] <= qpos[:, None, None]), s, -jnp.inf)
        return jnp.einsum("ths,shd->thd", jax.nn.softmax(s, axis=-1), vv)

    nb = T // _Q_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, _Q_BLOCK, H, D), jnp.arange(T).reshape(nb, _Q_BLOCK)))
    return out.reshape(T, H, D)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _layer(stack, i, x, cfg_key):
    cfg = dict(cfg_key)
    bits = cfg["act_bits"]
    H, KV, D = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    T = x.shape[0]
    pos = jnp.arange(T)
    ln = lambda name: jax.lax.dynamic_index_in_dim(stack[name]["scale"], i, keepdims=False)
    a, m = stack["attn"], stack["mlp"]
    h = _rms(x, ln("ln1"), cfg["eps"])
    q = _rope(_linear(a["wq"], h, bits, i).reshape(T, H, D), pos, cfg["theta"])
    k = _kv(_rope(_linear(a["wk"], h, bits, i).reshape(T, KV, D), pos, cfg["theta"]), cfg["kv_bits"])
    v = _kv(_linear(a["wv"], h, bits, i).reshape(T, KV, D), cfg["kv_bits"])
    x = x + _linear(a["wo"], _attention(q, k, v).reshape(T, H * D), bits, i)
    h = _rms(x, ln("ln2"), cfg["eps"])
    g = jax.nn.silu(_linear(m["w_gate"], h, bits, i)) * _linear(m["w_in"], h, bits, i)
    return x + _linear(m["w_out"], g, bits, i)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _gaps(params, x, rows, served, cfg_key):
    cfg = dict(cfg_key)
    h = _rms(x[rows], params["final_norm"]["scale"], cfg["eps"])
    if "head" in params:
        logits = _linear(params["head"], h, cfg["act_bits"])
    else:
        logits = h @ params["embed"]["table"].T
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got


def _cfg_key(cfg: dict) -> tuple:
    heads = cfg["num_attention_heads"]
    return tuple(sorted({
        "act_bits": cfg["a2q"]["act_bits"], "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "kv_bits": int(cfg.get("kv_bits", 0)),
    }.items()))


def gaps(params: dict, cfg: dict, tokens: np.ndarray, first: int, served: np.ndarray,
         pad_to: int = 0) -> np.ndarray:
    """For a sequence ``tokens`` whose positions ``first - 1 + j`` produced
    ``served[j]``, how far each served token's reference logit lies below the
    reference's best at its position (0 where they agree).  Sequences are
    padded at the end (causally invisible) to ``pad_to`` tokens, so that one
    compiled program serves every sequence of a cell."""
    key = _cfg_key(cfg)
    T, n = len(tokens), len(served)
    Tp = -(-max(T, pad_to) // _Q_BLOCK) * _Q_BLOCK
    tok = np.zeros((Tp,), np.int32)
    tok[:T] = tokens
    rows = np.full((Tp,), first - 1, np.int32)
    rows[:n] = np.arange(first - 1, first - 1 + n)
    want = np.zeros((Tp,), np.int32)
    want[:n] = served
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], jnp.asarray(tok), axis=0)
        stack = params["stacks"]["0"]
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(stack, jnp.int32(i), x, key)
        return np.asarray(_gaps(params, x, jnp.asarray(rows), jnp.asarray(want), key))[:n]
