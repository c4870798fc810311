"""Blocks and scan-over-layers stacks.

A model is a sequence of *stacks*; each stack is ``count`` identical blocks
compiled as one ``jax.lax.scan`` over stacked parameters (HLO size and compile
time O(1) in depth — essential for compiling 61-layer deepseek-v3 against 512
host devices).  Heterogeneous architectures (deepseek dense-then-MoE, llama4
local/global interleave) are expressed as multiple stacks.

Block kinds:
  * ``attn_mlp`` — pre-norm GQA/MLA + SwiGLU (or parallel attn+FFN, command-r)
  * ``moe``      — pre-norm attention + MoE FFN (+ shared experts)
  * ``rwkv6``    — time-mix + channel-mix
  * ``hymba``    — parallel SWA-attention and mamba(SSD) heads, then MLP

Every block returns ``(x, cache, a2q_penalty)``; the scan accumulates the
penalty so ``L_reg`` falls out of the forward pass for free.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, QuantConfig, StackConfig
from repro.nn.attention import apply_attention, attention_penalty, init_attention, init_attn_cache
from repro.nn.linear import (
    IntAct,
    apply_linear,
    chain_out_aq,
    init_linear,
    linear_penalty,
)
from repro.nn.moe import apply_moe, init_moe, moe_penalty
from repro.nn.module import unbox, with_layers_axis
from repro.nn.norms import apply_norm, init_norm
from repro.nn.ssm import (
    apply_mamba_heads,
    apply_rwkv6_channelmix,
    apply_rwkv6_timemix,
    init_mamba_heads,
    init_rwkv6_channelmix,
    init_rwkv6_timemix,
)

__all__ = ["init_block", "init_stack", "apply_stack", "init_stack_cache", "tree_a2q_penalty"]


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def _init_mlp(key, d: int, ff: int, q: QuantConfig, gated: bool, use_bias: bool) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_in": init_linear(ks[0], d, ff, q, axes=("embed", "mlp"), use_bias=use_bias),
        "w_out": init_linear(ks[1], ff, d, q, axes=("mlp", "embed"), use_bias=use_bias),
    }
    if gated:
        p["w_gate"] = init_linear(ks[2], d, ff, q, axes=("embed", "mlp"), use_bias=use_bias)
    return p


def _apply_mlp(p: dict, x, q: QuantConfig, compute_dtype,
               int_forward: bool = False, int_chain: bool = False) -> jnp.ndarray:
    lin = functools.partial(
        apply_linear, cfg=q, compute_dtype=compute_dtype,
        int_forward=int_forward, int_chain=int_chain,
    )
    if "w_gate" in p:
        # gated MLP: the silu(gate) * up product is a chain break (an fp
        # elementwise join of two linears), so every edge quantizes in its
        # own prologue — no int8 handoff exists here
        h = lin(p["w_in"], x=x, site="mlp.w_in")
        h = jax.nn.silu(
            lin(p["w_gate"], x=x, site="mlp.w_gate").astype(jnp.float32)
        ).astype(compute_dtype) * h
        return lin(p["w_out"], x=h, site="mlp.w_out")
    # non-gated MLP: w_in -> gelu -> w_out is a true producer/consumer chain;
    # w_in requantizes into w_out's quantizer in its epilogue (gelu replayed
    # in-register) and hands int8 codes across
    out_aq = (chain_out_aq(p["w_out"], q, act_fn="gelu") if int_chain else None)
    h = lin(p["w_in"], x=x, site="mlp.w_in", out_aq=out_aq)
    if not isinstance(h, IntAct):
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(compute_dtype)
    return lin(p["w_out"], x=h, site="mlp.w_out")


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_block(key, arch: ArchConfig, s: StackConfig) -> dict:
    """Boxed params of one block of stack ``s``."""
    d, q = arch.d_model, arch.quant
    ks = jax.random.split(key, 4)
    norm = lambda: init_norm(d, arch.norm)
    if s.kind in ("attn_mlp", "moe"):
        p = {"ln1": norm(), "attn": init_attention(ks[0], d, s.attn, q, arch.use_bias)}
        if not s.parallel_block:
            p["ln2"] = norm()
        if s.kind == "attn_mlp":
            p["mlp"] = _init_mlp(ks[1], d, s.d_ff, q, s.mlp_gated, arch.use_bias)
        else:
            p["moe"] = init_moe(ks[1], d, s.moe, q)
        return p
    if s.kind == "rwkv6":
        return {
            "ln1": norm(),
            "tm": init_rwkv6_timemix(ks[0], d, s.ssm, q),
            "ln2": norm(),
            "cm": init_rwkv6_channelmix(ks[1], d, s.d_ff, q),
        }
    if s.kind == "hymba":
        return {
            "ln1": norm(),
            "attn": init_attention(ks[0], d, s.attn, q, arch.use_bias),
            "mamba": init_mamba_heads(ks[1], d, s.ssm, q),
            "ln2": norm(),
            "mlp": _init_mlp(ks[2], d, s.d_ff, q, s.mlp_gated, arch.use_bias),
        }
    raise ValueError(s.kind)


def _apply_block(
    p: dict,
    x: jnp.ndarray,
    arch: ArchConfig,
    s: StackConfig,
    positions: jnp.ndarray,
    cache: Optional[dict],
    *,
    mesh=None,
    ep_axis: Optional[str] = None,
    mla_absorb: bool = False,
    view: Optional[dict] = None,
    decode_kernel: bool = False,
    int_forward: bool = False,
    int_chain: bool = False,
):
    q = arch.quant
    cd = jnp.dtype(arch.compute_dtype)
    norm = functools.partial(apply_norm, kind=arch.norm, eps=arch.norm_eps)
    new_cache: dict = {}
    if s.kind in ("attn_mlp", "moe"):
        h = norm(p["ln1"], x)
        with jax.named_scope("attention"):
            attn_out, c = apply_attention(
                p["attn"], h, s.attn, q, positions, (cache or {}).get("attn"),
                q_chunk=arch.attn_q_chunk, compute_dtype=cd, mla_absorb=mla_absorb,
                view=view, decode_kernel=decode_kernel, int_forward=int_forward,
                int_chain=int_chain,
            )
        if c is not None:
            new_cache["attn"] = c

        def ffn_of(hx):
            with jax.named_scope("mlp"):
                if s.kind == "moe":
                    return apply_moe(p["moe"], hx, s.moe, q, ep_axis=ep_axis, mesh=mesh,
                                     compute_dtype=cd, int_forward=int_forward,
                                     int_chain=int_chain)
                return _apply_mlp(p["mlp"], hx, q, cd, int_forward, int_chain)

        if s.parallel_block:
            x = x + attn_out + ffn_of(h)
        else:
            x = x + attn_out
            x = x + ffn_of(norm(p["ln2"], x))
    elif s.kind == "rwkv6":
        h = norm(p["ln1"], x)
        y, c = apply_rwkv6_timemix(p["tm"], h, s.ssm, q, (cache or {}).get("tm"), compute_dtype=cd, int_forward=int_forward, int_chain=int_chain)
        if c is not None:
            new_cache["tm"] = c
        x = x + y
        h2 = norm(p["ln2"], x)
        y2, c2 = apply_rwkv6_channelmix(p["cm"], h2, q, (cache or {}).get("cm"), compute_dtype=cd, int_forward=int_forward, int_chain=int_chain)
        if c2 is not None:
            new_cache["cm"] = c2
        x = x + y2
    elif s.kind == "hymba":
        h = norm(p["ln1"], x)
        with jax.named_scope("attention"):
            attn_out, c = apply_attention(
                p["attn"], h, s.attn, q, positions, (cache or {}).get("attn"),
                q_chunk=arch.attn_q_chunk, compute_dtype=cd,
                view=view, decode_kernel=decode_kernel, int_forward=int_forward,
                int_chain=int_chain,
            )
        if c is not None:
            new_cache["attn"] = c
        m_out, cm = apply_mamba_heads(p["mamba"], h, s.ssm, q, (cache or {}).get("mamba"), compute_dtype=cd, int_forward=int_forward, int_chain=int_chain)
        if cm is not None:
            new_cache["mamba"] = cm
        x = x + 0.5 * (attn_out + m_out)
        h2 = norm(p["ln2"], x)
        with jax.named_scope("mlp"):
            ffn = _apply_mlp(p["mlp"], h2, q, cd, int_forward, int_chain)
        x = x + ffn
    else:
        raise ValueError(s.kind)

    penalty = tree_a2q_penalty(p, q)
    return x, (new_cache or None), penalty


# Param subtrees whose matmul consumes *unsigned* activations (post-relu^2):
_UNSIGNED_LEAF_NAMES = {"wv_channelmix"}


def tree_a2q_penalty(p, q: QuantConfig) -> jnp.ndarray:
    """Walk a block's params and sum every A2Q layer's regularizer.

    The channel-mix ``wv`` (post-relu^2, unsigned input) is the one layer whose
    cap uses 1_signed = 0; all other transformer matmuls see signed inputs.
    """
    total = jnp.zeros((), jnp.float32)
    if q.mode != "a2q":
        return total

    def walk(node, path):
        nonlocal total
        if isinstance(node, dict):
            if "t" in node and "d" in node and "v" in node:
                signed = not (len(path) >= 2 and path[-2] == "cm" and path[-1] == "wv")
                if node["t"].ndim == 2:  # stacked experts (E, C)
                    from repro.core.a2q import a2q_norm_cap

                    T = a2q_norm_cap(node["d"], q.acc_bits, q.act_bits, signed)
                    total = total + jnp.sum(jnp.maximum(node["t"] - T, 0.0))
                else:
                    total = total + linear_penalty(node, q, False, signed)
            else:
                for k, v in node.items():
                    walk(v, path + (k,))

    walk(p, ())
    return total


# ---------------------------------------------------------------------------
# Stacks: vmapped init, scanned apply
# ---------------------------------------------------------------------------


def init_stack(key, arch: ArchConfig, s: StackConfig):
    """Stacked (leading ``count`` dim) boxed params for one stack."""
    keys = jax.random.split(key, s.count)
    stacked = jax.vmap(lambda k: init_block(k, arch, s))(keys)
    return with_layers_axis(stacked)


def apply_stack(
    params,
    x: jnp.ndarray,
    arch: ArchConfig,
    s: StackConfig,
    positions: jnp.ndarray,
    cache=None,
    *,
    mesh=None,
    ep_axis: Optional[str] = None,
    mla_absorb: bool = False,
    view: Optional[dict] = None,
    decode_kernel: bool = False,
    int_forward: bool = False,
    int_chain: bool = False,
):
    """Scan ``s.count`` blocks.  Returns (x, new_cache, total_penalty).

    ``view`` (the paged block-table, shared by every layer), ``decode_kernel``
    and ``int_forward``/``int_chain`` (the fused W8A8 serve path and its
    int8-out chaining) pass straight through to the attention / linear
    layers.  Chained activations never cross a block boundary (every block
    ends in a residual add — a chain break), so the scan carry stays fp.
    """

    def body(carry, layer_in):
        xc = carry
        layer_params, layer_cache = layer_in
        xn, new_cache, pen = _apply_block(
            layer_params, xc, arch, s, positions, layer_cache,
            mesh=mesh, ep_axis=ep_axis, mla_absorb=mla_absorb,
            view=view, decode_kernel=decode_kernel, int_forward=int_forward,
            int_chain=int_chain,
        )
        return xn, (new_cache, pen)

    if arch.remat != "none":
        body = jax.checkpoint(body, prevent_cse=False)

    if s.count == 1 or arch.unroll_stacks:
        # Python loop: singleton stacks, and the roofline costing variants
        # (XLA cost_analysis counts a scan body once, so per-layer costs are
        # measured on unrolled models — see launch/dryrun.py).
        new_caches, pens = [], []
        xc = x
        for i in range(s.count):
            lp = jax.tree.map(lambda a: a[i], params)
            lc = jax.tree.map(lambda a: a[i], cache) if cache is not None else None
            xc, (nc, pen) = body(xc, (lp, lc))
            new_caches.append(nc)
            pens.append(pen)
        if new_caches[0] is not None:
            new_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *new_caches)
        else:
            new_cache = None
        return xc, new_cache, sum(pens)

    x, (new_cache, pens) = jax.lax.scan(body, x, (params, cache))
    return x, new_cache, jnp.sum(pens)


def init_stack_cache(arch: ArchConfig, s: StackConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Stacked decode cache for one stack (leading dim = s.count)."""
    d = arch.d_model

    def one():
        if s.kind in ("attn_mlp", "moe"):
            return {"attn": init_attn_cache(batch, s.attn, max_seq, dtype)}
        if s.kind == "rwkv6":
            H = d // s.ssm.head_dim
            return {
                "tm": {
                    "S": jnp.zeros((batch, H, s.ssm.head_dim, s.ssm.head_dim), jnp.float32),
                    "shift": jnp.zeros((batch, 1, d), dtype),
                },
                "cm": {"shift": jnp.zeros((batch, 1, d), dtype)},
            }
        if s.kind == "hymba":
            H = d // s.ssm.head_dim
            return {
                "attn": init_attn_cache(batch, s.attn, max_seq, dtype),
                "mamba": {"S": jnp.zeros((batch, H, s.ssm.head_dim, s.ssm.state_dim), jnp.float32)},
            }
        raise ValueError(s.kind)

    cache = one()
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (s.count, *a.shape)), cache)
