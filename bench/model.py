"""A configuration file as the program's ``ArchConfig``, and its weights made
from the seed on the device in one jitted call.

The weights belong to the benchmark, not to the program: the plain
reference (``reference/``) reads the same arrays, and nothing the program
computes.  They follow the paper's A2Q deployment (Colbert et al., arXiv
2308.13504, Eq. 15-23): per output channel, a Gaussian draw concentrated on
its largest entries, the l1 norm capped so that every partial sum of
``act_bits``-bit inputs fits an ``acc_bits``-bit accumulator, rounded toward
zero to ``weight_bits``-bit codes with a power-of-two scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtri

from repro.configs.base import ArchConfig, AttnConfig, QuantConfig, StackConfig


def arch_from_config(cfg: dict) -> ArchConfig:
    """The program's configuration object for a configuration file."""
    heads = cfg["num_attention_heads"]
    q = cfg["a2q"]
    return ArchConfig(
        name=cfg["name"],
        family="lm",
        d_model=cfg["hidden_size"],
        vocab=cfg["vocab_size"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]),
        stacks=(StackConfig(
            kind="attn_mlp",
            count=cfg["num_hidden_layers"],
            attn=AttnConfig(heads=heads, kv_heads=cfg["num_key_value_heads"],
                            head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
                            rope_theta=float(cfg["rope_theta"])),
            d_ff=cfg["intermediate_size"],
        ),),
        quant=QuantConfig(mode="a2q", weight_bits=q["weight_bits"], act_bits=q["act_bits"],
                          acc_bits=q["acc_bits"], reg_lambda=q["reg_lambda"]),
        compute_dtype=cfg["compute_dtype"],
        remat=cfg.get("remat", "block"),
    )


def linear_shapes(cfg: dict) -> dict:
    """``(d_in, d_out)`` of each per-layer linear, by its name in the tree."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim", d // cfg["num_attention_heads"])
    qd, kd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {("attn", "wq"): (d, qd), ("attn", "wk"): (d, kd), ("attn", "wv"): (d, kd),
            ("attn", "wo"): (qd, d), ("mlp", "w_in"): (d, ff), ("mlp", "w_gate"): (d, ff),
            ("mlp", "w_out"): (ff, d)}


_CAP_MARGIN = 2.0 ** -10


def _a2q(key, d_in: int, d_out: int, q: dict):
    """One A2Q linear from a Gaussian draw: ``(v, t, d)`` (training form)."""
    bits, acc, n_in = q["weight_bits"], q["acc_bits"], q["act_bits"]
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * math.sqrt(2.0 / d_in)
    # the accumulator admits at most floor(budget) unit codes per channel:
    # keep about that many of the largest entries so that the channel
    # survives rounding toward zero
    budget = (2.0 ** (acc - 1) - 1.0) * 2.0 ** (1 - n_in)
    keep = min(1.0, math.floor(budget) / d_in)
    if keep < 1.0:
        z = float(ndtri(1.0 - keep / 2.0)) * math.sqrt(2.0 / d_in)
        w = jnp.where(jnp.abs(w) >= z, w, 0.0)
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8)
    l1 = jnp.maximum(jnp.sum(jnp.abs(w), axis=0), 1e-8)
    d = jnp.log2(absmax / (2 ** (bits - 1) - 1))
    cap = 1.0 + math.log2(2.0 ** (acc - 1) - 1.0) + d - n_in
    # a norm a little under its cap, not on it: at the cap, which side of
    # min(t, T) the gradient takes rests on the last bit of T
    t = jnp.minimum(jnp.log2(l1), cap - _CAP_MARGIN)
    return w, t, d


def _deploy(v, t, d, q: dict):
    """Codes and scale of an A2Q linear (rounding toward zero)."""
    bits, acc, n_in = q["weight_bits"], q["acc_bits"], q["act_bits"]
    cap = 1.0 + math.log2(2.0 ** (acc - 1) - 1.0) + d - n_in
    g_over_s = jnp.exp2(jnp.minimum(t, cap) - d)
    l1 = jnp.maximum(jnp.sum(jnp.abs(v), axis=0), 1e-12)
    lim = 2 ** (bits - 1)
    codes = jnp.clip(jnp.trunc(g_over_s * v / l1), -lim, lim - 1)
    return codes.astype(jnp.int8), jnp.exp2(d)


def _act_scale(q: dict) -> jnp.ndarray:
    return jnp.log2(jnp.float32(q["act_absmax"] / (2 ** (q["act_bits"] - 1) - 1)))


def _layer(key, cfg: dict, deployed: bool) -> dict:
    q = cfg["a2q"]
    d = cfg["hidden_size"]
    out: dict = {"ln1": {"scale": jnp.ones((d,), jnp.float32)},
                 "ln2": {"scale": jnp.ones((d,), jnp.float32)}, "attn": {}, "mlp": {}}
    keys = jax.random.split(key, 7)
    for k, ((grp, name), (din, dout)) in zip(keys, linear_shapes(cfg).items()):
        v, t, dd = _a2q(k, din, dout, q)
        if deployed:
            codes, scale = _deploy(v, t, dd, q)
            leaf = {"q8": codes, "s8": scale}
        else:
            leaf = {"v": v, "t": t, "d": dd}
        leaf["aq"] = {"log2_scale": _act_scale(q)}
        out[grp][name] = leaf
    return out


def make_params(cfg: dict, seed: int, *, deployed: bool, shardings=None) -> dict:
    """The model's weights from ``seed``, built on the device in one jitted
    program: int8 codes and scales (``deployed``, the serving artifact) or
    the A2Q training parameters ``(v, t, d)``.  The layer stack is a
    ``lax.map``, so one layer's float draw is live at a time.  ``shardings``
    (a tree like the weights') places them across a mesh as they are made."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    q = cfg["a2q"]

    def build(key):
        k_emb, k_layers, k_head = jax.random.split(key, 3)
        layers = jax.lax.map(lambda k: _layer(k, cfg, deployed), jax.random.split(k_layers, L))
        p = {"embed": {"table": jax.random.normal(k_emb, (V, d), jnp.float32) * 0.02},
             "stacks": {"0": layers},
             "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}
        if not cfg["tie_word_embeddings"]:
            v, t, dd = _a2q(k_head, d, V, q)
            if deployed:
                codes, scale = _deploy(v, t, dd, q)
                p["head"] = {"q8": codes, "s8": scale}
            else:
                p["head"] = {"v": v, "t": t, "d": dd}
            p["head"]["aq"] = {"log2_scale": _act_scale(q)}
        return p

    return jax.jit(build, out_shardings=shardings)(prng_key(seed))


def prng_key(seed: int):
    """A JAX key for any non-negative seed, 64-bit ones included."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
