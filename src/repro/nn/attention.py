"""Attention layers: GQA (+RoPE/NoPE, sliding-window, chunked-local) and MLA.

All projections are QuantLinear instances, so A2Q attaches to q/k/v/o (and the
MLA down/up projections) exactly as to any other matmul (DESIGN.md Sec. 5).

The softmax path is the memory-bounded *query-chunked* jnp implementation —
``lax.map`` over query blocks keeps the live score buffer at
``(B, tc, H, S)`` — which is both the CPU/dry-run execution path and the
oracle for the Pallas flash kernel (``kernels/flash_attention.py``, the TPU
fast path).

KV caches (the *cache view* interface — all layouts share one ``_sdpa``):
* full      — ``(B, S_max, KV, Dh)``, decode writes at ``pos``;
* ring      — ``(B, W, KV, Dh)`` for sliding-window / chunked-local layers;
  slot ``pos % W`` plus an explicit per-slot absolute-position array, so a
  500k-token decode holds only W entries (this is what makes h2o-danube /
  hymba / llama4-local long-context cells runnable);
* MLA       — compressed latent ``(B, S_max, kv_lora)`` + shared rope key;
* paged     — pools of fixed-size token blocks ``(NB, bs, KV, Dh)`` (keys
  ``kp``/``vp``; MLA: ``ckvp``/``kpep``) indexed through a per-sequence block
  table ``view["bt"] (B, MB)`` owned by ``serve/paged_cache.py``.  Cache
  memory scales with live tokens instead of ``batch x max_seq``.
* paged int8 — the same pools stored as int8 codes next to per-slot fp32
  scale pools (``kps``/``vps``; MLA: ``ckvs``/``kpes``), detected by the
  scale keys.  K/V are quantized on write (one scale per token per KV head,
  absmax over the head dim) and dequantized on read — in-register inside the
  Pallas decode kernel, on the gathered view otherwise — cutting KV HBM
  footprint and decode bandwidth ~4x.

Cache updates accept ``T >= 1`` tokens per call (chunked prefill): non-ring
caches write a contiguous span at each row's start position, ring caches
scatter modulo the window, paged caches scatter through the block table.

Masking is always computed from *absolute* positions (slot positions for ring
caches, block-table positions for paged ones), so every layout and decode
path shares one `_sdpa`.  The paged decode read has two executions: the
gathered-view ``_sdpa`` (portable truth, bit-identical to the contiguous
layout) and the Pallas kernel ``kernels/paged_attention.py`` selected with
``decode_kernel=True`` (the TPU fast path — no materialized gather).
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig, QuantConfig
from repro.nn.embedding import apply_rope
from repro.nn.linear import apply_linear, init_linear, linear_penalty
from repro.nn.norms import apply_norm, init_norm

__all__ = [
    "init_attention",
    "apply_attention",
    "init_attn_cache",
    "attention_penalty",
]

_NEG = -1e30


# ---------------------------------------------------------------------------
# Core scaled-dot-product with absolute-position masking, grouped KV heads,
# and query chunking.
# ---------------------------------------------------------------------------


def _sdpa(
    q: jnp.ndarray,  # (B, T, H, Dh)
    k: jnp.ndarray,  # (B, S, KV, Dh)
    v: jnp.ndarray,  # (B, S, KV, Dv)
    qpos: jnp.ndarray,  # (B, T) absolute positions
    kpos: jnp.ndarray,  # (B, S) absolute positions, -1 = empty slot
    *,
    causal: bool,
    window: Optional[int],
    chunk: Optional[int],
    q_chunk: int,
) -> jnp.ndarray:
    B, T, H, Dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]  # may differ from Dh (MLA: nope+rope query vs v_head_dim)
    G = H // KV
    scale = Dh**-0.5

    def block(q_c: jnp.ndarray, qpos_c: jnp.ndarray) -> jnp.ndarray:
        # q_c (B, tc, KV, G, Dh); qpos_c (B, tc)
        s = jnp.einsum(
            "btkgd,bskd->btkgs",
            q_c.astype(jnp.float32) * scale,
            k.astype(jnp.float32),
        )
        qp = qpos_c[:, :, None]  # (B, tc, 1)
        kp = kpos[:, None, :]  # (B, 1, S)
        mask = kp >= 0
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        if chunk is not None:
            mask &= (kp // chunk) == (qp // chunk)
        m4 = mask[:, :, None, None, :]
        s = jnp.where(m4, s, _NEG)
        s_max = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - s_max)
        p = jnp.where(m4, p, 0.0)
        denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("btkgs,bskd->btkgd", p / denom, v.astype(jnp.float32))
        return o

    qg = q.reshape(B, T, KV, G, Dh)
    if T <= q_chunk:
        out = block(qg, qpos)
    else:
        nc, rem = divmod(T, q_chunk)
        Tm = nc * q_chunk
        q_blocks = qg[:, :Tm].reshape(B, nc, q_chunk, KV, G, Dh).swapaxes(0, 1)
        p_blocks = qpos[:, :Tm].reshape(B, nc, q_chunk).swapaxes(0, 1)
        out = jax.lax.map(lambda args: block(*args), (q_blocks, p_blocks))
        out = out.swapaxes(0, 1).reshape(B, Tm, KV, G, Dv)
        if rem:
            out = jnp.concatenate([out, block(qg[:, Tm:], qpos[:, Tm:])], axis=1)
    return out.reshape(B, T, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer
# ---------------------------------------------------------------------------


def _init_gqa(key, d_model: int, a: AttnConfig, q: QuantConfig, use_bias: bool) -> dict:
    ks = jax.random.split(key, 4)
    HD, KD = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return {
        "wq": init_linear(ks[0], d_model, HD, q, axes=("embed", "heads"), use_bias=use_bias),
        "wk": init_linear(ks[1], d_model, KD, q, axes=("embed", "kv_heads"), use_bias=use_bias),
        "wv": init_linear(ks[2], d_model, KD, q, axes=("embed", "kv_heads"), use_bias=use_bias),
        "wo": init_linear(ks[3], HD, d_model, q, axes=("heads", "embed"), use_bias=use_bias),
    }


def _init_mla(key, d_model: int, a: AttnConfig, q: QuantConfig) -> dict:
    ks = jax.random.split(key, 5)
    qh = a.qk_nope_dim + a.qk_rope_dim
    return {
        "wq_a": init_linear(ks[0], d_model, a.q_lora_rank, q, axes=("embed", None)),
        "q_norm": init_norm(a.q_lora_rank, "rmsnorm", axis_name=None),
        "wq_b": init_linear(ks[1], a.q_lora_rank, a.heads * qh, q, axes=(None, "heads")),
        "wkv_a": init_linear(
            ks[2], d_model, a.kv_lora_rank + a.qk_rope_dim, q, axes=("embed", None)
        ),
        "kv_norm": init_norm(a.kv_lora_rank, "rmsnorm", axis_name=None),
        "wkv_b": init_linear(
            ks[3], a.kv_lora_rank, a.heads * (a.qk_nope_dim + a.v_head_dim), q,
            axes=(None, "heads"),
        ),
        "wo": init_linear(ks[4], a.heads * a.v_head_dim, d_model, q, axes=("heads", "embed")),
    }


def init_attention(key, d_model: int, a: AttnConfig, q: QuantConfig, use_bias: bool = False) -> dict:
    if a.kind == "mla":
        return _init_mla(key, d_model, a, q)
    return _init_gqa(key, d_model, a, q, use_bias)


def init_attn_cache(
    batch: int, a: AttnConfig, max_seq: int, dtype=jnp.bfloat16
) -> dict:
    """Allocate the decode cache for one layer of this attention kind."""
    if a.kind == "mla":
        return {
            "ckv": jnp.zeros((batch, max_seq, a.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_seq, a.qk_rope_dim), dtype),
            "kpos": jnp.full((batch, max_seq), -1, jnp.int32),
        }
    slots = max_seq
    ring = a.window or a.chunk
    if ring is not None:
        slots = min(ring, max_seq)
    return {
        "k": jnp.zeros((batch, slots, a.kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, slots, a.kv_heads, a.head_dim), dtype),
        "kpos": jnp.full((batch, slots), -1, jnp.int32),
    }


def _write_cache(cache: dict, updates: dict, pos: jnp.ndarray, ring: bool) -> dict:
    """Write a ``T``-token update into the cache (``T == 1`` decode, ``T > 1``
    chunked prefill).

    ``pos`` may be a scalar or a per-row ``(B,)`` vector of *start* positions
    — the serve engine's continuous batching advances slots at different
    positions, so writes are vmapped per batch row.  Non-ring caches take a
    contiguous ``[pos, pos + T)`` span; ring caches scatter at
    ``(pos + t) % slots``.
    """
    new = dict(cache)
    B, slots = cache["kpos"].shape
    T = next(iter(updates.values())).shape[1]
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    abs_pos = pos_vec[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)

    if ring:
        slot_idx = abs_pos % slots
        if T > slots:
            # A chunk longer than the ring maps several tokens to one slot
            # (t and t + slots).  Scatter order for duplicate indices is
            # implementation-defined, so drop every write a later token in
            # this chunk supersedes: only t >= T - slots survive (redirected
            # out of range otherwise, removed by mode="drop").
            keep = jnp.arange(T, dtype=jnp.int32) >= T - slots
            slot_idx = jnp.where(keep[None, :], slot_idx, slots)

        def write_row(c_row, u_row, s):
            return c_row.at[s].set(u_row, mode="drop")

    else:
        slot_idx = abs_pos

        def write_row(c_row, u_row, s):
            start = (s[0],) + (0,) * (c_row.ndim - 1)
            return jax.lax.dynamic_update_slice(c_row, u_row, start)

    for name, val in updates.items():  # val (B, T, ...)
        new[name] = jax.vmap(write_row)(cache[name], val.astype(cache[name].dtype), slot_idx)
    new["kpos"] = jax.vmap(write_row)(cache["kpos"], abs_pos, slot_idx)
    return new


# ---------------------------------------------------------------------------
# Paged cache view: block pools indexed through per-sequence block tables.
# ---------------------------------------------------------------------------


def _paged_write(pool: jnp.ndarray, val: jnp.ndarray, bt: jnp.ndarray, abs_pos: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``val (B, T, ...)`` into ``pool (NB, bs, ...)`` at the blocks the
    table assigns: token at absolute position p lands in
    ``pool[bt[b, p // bs], p % bs]``.  Rows never share live blocks (the
    allocator hands each sequence its own), so writes cannot collide except in
    the reserved trash block that dead slots point at."""
    bs = pool.shape[1]
    blk = jnp.take_along_axis(bt, abs_pos // bs, axis=1)  # (B, T)
    off = abs_pos % bs
    return pool.at[blk, off].set(val.astype(pool.dtype), mode="drop")


def _paged_gather(pool: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    """Materialize the per-row contiguous view ``(B, MB * bs, ...)`` of a pool
    through the block table.  Because the allocator assigns a sequence's
    blocks in logical order, row b of the result is exactly the contiguous
    cache lane the non-paged layout would hold — the portable decode path and
    the oracle for the Pallas paged-attention kernel."""
    B, MB = bt.shape
    g = pool[bt]  # (B, MB, bs, ...)
    return g.reshape(B, MB * pool.shape[1], *pool.shape[2:])


def _paged_kpos(positions: jnp.ndarray, S: int) -> jnp.ndarray:
    """Absolute key positions of the gathered view: ``[0, len)`` valid, -1
    beyond, where ``len`` = each row's position after this call's write."""
    new_len = positions[:, -1] + 1  # (B,)
    ar = jnp.arange(S, dtype=jnp.int32)[None, :]
    return jnp.where(ar < new_len[:, None], ar, -1)


def _kv_quantize(val: jnp.ndarray, bits: int = 8) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric ``bits``-bit quantization of a K/V update along its feature
    dim: ``val (B, T, ..., D)`` -> (codes int8 in [-qmax, qmax], per-
    ``(B, T, ...)`` fp32 scales).  One scale per written token (per KV head
    for GQA pools, per latent row for MLA), absmax-calibrated — the write is
    the only time the fp value exists, so quantize-on-write is the whole
    encoder."""
    qmax = (1 << (bits - 1)) - 1  # 127 (int8) or 7 (int4)
    vf = val.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / qmax
    codes = jnp.clip(jnp.round(vf / scale[..., None]), -qmax, qmax).astype(jnp.int8)
    return codes, scale


def _pack_nibbles(codes: jnp.ndarray) -> jnp.ndarray:
    """int4 codes ``(..., D)`` (int8 values in [-7, 7]) -> packed uint8
    ``(..., D // 2)``: element 2i in the low nibble, 2i+1 in the high."""
    u = codes.astype(jnp.uint8) & 0xF
    return (u[..., 0::2] | (u[..., 1::2] << 4)).astype(jnp.uint8)


def _unpack_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    """Packed uint8 ``(..., D // 2)`` -> sign-extended int32 ``(..., D)``."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    se = lambda x: (x ^ 8) - 8  # 4-bit two's-complement sign extension
    out = jnp.stack([se(lo), se(hi)], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def _paged_write_q8(
    pool: jnp.ndarray,
    scales: jnp.ndarray,
    val: jnp.ndarray,
    bt: jnp.ndarray,
    abs_pos: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize-on-write into an integer pool + its per-slot scale pool.
    An int8 pool stores the codes directly; a uint8 pool is the packed int4
    layout (two codes per byte, half the feature width) — detected by dtype,
    so the scale-pool machinery is byte-width agnostic."""
    if pool.dtype == jnp.uint8:
        codes, s = _kv_quantize(val, bits=4)
        codes = _pack_nibbles(codes)
    else:
        codes, s = _kv_quantize(val, bits=8)
    return _paged_write(pool, codes, bt, abs_pos), _paged_write(scales, s, bt, abs_pos)


def _paged_gather_deq(pool: jnp.ndarray, scales: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    """Gathered contiguous view of an integer pool, dequantized against its
    per-slot scales (fp32) — the portable read path and the oracle layout for
    the q8 decode kernel.  uint8 pools are the packed int4 layout and are
    unpacked before the rescale."""
    g = _paged_gather(pool, bt)
    if pool.dtype == jnp.uint8:
        g = _unpack_nibbles(g)
    return g.astype(jnp.float32) * _paged_gather(scales, bt)[..., None]


def apply_attention(
    params: dict,
    x: jnp.ndarray,
    a: AttnConfig,
    q: QuantConfig,
    positions: jnp.ndarray,  # (B, T) absolute
    cache: Optional[dict] = None,
    *,
    q_chunk: int = 256,
    compute_dtype=jnp.bfloat16,
    mla_absorb: bool = False,
    view: Optional[dict] = None,
    decode_kernel: bool = False,
    int_forward: bool = False,
    int_chain: bool = False,
) -> tuple[jnp.ndarray, Optional[dict]]:
    """Returns (output, updated cache).  ``cache`` given => cached step over
    ``T >= 1`` new tokens (decode or chunked prefill).  A paged cache (keys
    ``kp``/``vp`` or ``ckvp``/``kpep``) additionally needs the block-table
    ``view``; ``decode_kernel=True`` routes the paged ``T == 1`` read through
    the Pallas paged-attention kernel instead of the gathered-view ``_sdpa``.
    ``int_forward`` routes deployed projections through the fused W8A8 path.

    Every attention projection is a chain break — wq/wk/wv feed rope + the
    attention core and wo sits behind it — so ``int_chain`` folds each
    act-quant into the kernel prologue (no int8 handoff between them).
    """
    if a.kind == "mla":
        return _apply_mla(
            params, x, a, q, positions, cache,
            q_chunk=q_chunk, compute_dtype=compute_dtype, absorb=mla_absorb,
            view=view, decode_kernel=decode_kernel, int_forward=int_forward,
            int_chain=int_chain,
        )
    B, T, D = x.shape
    H, KV, Dh = a.heads, a.kv_heads, a.head_dim
    lin = functools.partial(
        apply_linear, cfg=q, compute_dtype=compute_dtype,
        int_forward=int_forward, int_chain=int_chain,
    )
    qh = lin(params["wq"], x=x, site="attn.wq").reshape(B, T, H, Dh)
    kh = lin(params["wk"], x=x, site="attn.wk").reshape(B, T, KV, Dh)
    vh = lin(params["wv"], x=x, site="attn.wv").reshape(B, T, KV, Dh)
    if a.rope_theta is not None:
        qh = apply_rope(qh, positions, a.rope_theta)
        kh = apply_rope(kh, positions, a.rope_theta)

    if cache is None:
        kpos = jnp.where(jnp.ones((B, T), bool), positions, -1)
        out = _sdpa(
            qh, kh, vh, positions, kpos,
            causal=a.causal, window=a.window, chunk=a.chunk, q_chunk=q_chunk,
        )
        new_cache = None
    elif "kp" in cache:  # paged view
        assert view is not None, "paged attention cache needs a block-table view"
        bt = view["bt"]
        quant = "kps" in cache  # int8 pools carry per-slot scale pools
        with jax.named_scope("kv_write"):
            if quant:
                kp_new, kps_new = _paged_write_q8(cache["kp"], cache["kps"], kh, bt, positions)
                vp_new, vps_new = _paged_write_q8(cache["vp"], cache["vps"], vh, bt, positions)
                new_cache = {"kp": kp_new, "kps": kps_new, "vp": vp_new, "vps": vps_new}
            else:
                new_cache = {
                    "kp": _paged_write(cache["kp"], kh, bt, positions),
                    "vp": _paged_write(cache["vp"], vh, bt, positions),
                }
        # int8 and packed-int4 pools both ride the kernel (it detects the
        # byte width from the pool dtype); windowed decode is covered via
        # the kernel's window mask
        kernel_ok = decode_kernel and T == 1 and a.causal and a.chunk is None
        if kernel_ok:
            from repro.kernels import ops

            out = ops.paged_attention(
                qh[:, 0], new_cache["kp"], new_cache["vp"], bt, positions[:, 0] + 1,
                kps=new_cache.get("kps"), vps=new_cache.get("vps"), window=a.window,
            )[:, None]
        else:
            if quant:
                k_all = _paged_gather_deq(new_cache["kp"], new_cache["kps"], bt)
                v_all = _paged_gather_deq(new_cache["vp"], new_cache["vps"], bt)
            else:
                k_all = _paged_gather(new_cache["kp"], bt)
                v_all = _paged_gather(new_cache["vp"], bt)
            kpos = _paged_kpos(positions, k_all.shape[1])
            out = _sdpa(
                qh, k_all, v_all, positions, kpos,
                causal=a.causal, window=a.window, chunk=a.chunk, q_chunk=q_chunk,
            )
    else:
        ring = (a.window or a.chunk) is not None
        new_cache = _write_cache(cache, {"k": kh, "v": vh}, positions[:, 0], ring)
        if ring and T > 1:
            # Chunked prefill over a ring: the chunk's own writes overwrite
            # slots whose keys the chunk's *early* queries still need (any
            # position in [start - W + T', start) for later offsets T').
            # Attend the pre-write ring snapshot + the chunk's fresh K/V
            # instead — absolute-position masking drops stale/out-of-window
            # entries, and ctx positions (< start) never collide with chunk
            # positions.
            k_all = jnp.concatenate([cache["k"], kh.astype(cache["k"].dtype)], axis=1)
            v_all = jnp.concatenate([cache["v"], vh.astype(cache["v"].dtype)], axis=1)
            kpos = jnp.concatenate([cache["kpos"], positions], axis=1)
        else:
            k_all, v_all, kpos = new_cache["k"], new_cache["v"], new_cache["kpos"]
        out = _sdpa(
            qh, k_all, v_all, positions, kpos,
            causal=a.causal, window=a.window, chunk=a.chunk, q_chunk=q_chunk,
        )
    out = out.reshape(B, T, H * Dh)
    return lin(params["wo"], x=out, site="attn.wo"), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v3): low-rank compressed q and kv, shared rope key.
# ---------------------------------------------------------------------------


def _apply_mla(
    params: dict,
    x: jnp.ndarray,
    a: AttnConfig,
    q: QuantConfig,
    positions: jnp.ndarray,
    cache: Optional[dict],
    *,
    q_chunk: int,
    compute_dtype,
    absorb: bool,
    view: Optional[dict] = None,
    decode_kernel: bool = False,
    int_forward: bool = False,
    int_chain: bool = False,
) -> tuple[jnp.ndarray, Optional[dict]]:
    B, T, D = x.shape
    H = a.heads
    nope, rope, vd = a.qk_nope_dim, a.qk_rope_dim, a.v_head_dim
    # All MLA projections are chain breaks: norms, rope, reshapes, and the
    # attention core sit between every producer/consumer pair.
    lin = functools.partial(
        apply_linear, cfg=q, compute_dtype=compute_dtype,
        int_forward=int_forward, int_chain=int_chain,
    )

    cq = apply_norm(params["q_norm"], lin(params["wq_a"], x=x, site="mla.wq_a"))
    qh = lin(params["wq_b"], x=cq, site="mla.wq_b").reshape(B, T, H, nope + rope)
    q_nope, q_pe = qh[..., :nope], qh[..., nope:]
    q_pe = apply_rope(q_pe, positions, a.rope_theta or 10000.0)

    kv_a = lin(params["wkv_a"], x=x, site="mla.wkv_a")
    ckv = apply_norm(params["kv_norm"], kv_a[..., : a.kv_lora_rank])
    kpe = kv_a[..., a.kv_lora_rank :].reshape(B, T, 1, rope)
    kpe = apply_rope(kpe, positions, a.rope_theta or 10000.0).reshape(B, T, rope)

    # Absorbed single-token decode over a paged latent cache routes through
    # the Pallas MLA latent-attention kernel: scores and PV run directly on
    # the pool blocks, so the gathered (B, S, R) latent view is never built.
    use_kernel = (
        decode_kernel and absorb and T == 1 and a.causal
        and cache is not None and "ckvp" in cache
    )
    if cache is not None and "ckvp" in cache:  # paged latent cache
        assert view is not None, "paged MLA cache needs a block-table view"
        bt = view["bt"]
        if "ckvs" in cache:  # int8 latent pools, per-token fp32 scales
            with jax.named_scope("kv_write"):
                ckvp_new, ckvs_new = _paged_write_q8(cache["ckvp"], cache["ckvs"], ckv, bt, positions)
                kpep_new, kpes_new = _paged_write_q8(cache["kpep"], cache["kpes"], kpe, bt, positions)
            cache = {"ckvp": ckvp_new, "ckvs": ckvs_new, "kpep": kpep_new, "kpes": kpes_new}
            if not use_kernel:
                ckv_all = _paged_gather_deq(cache["ckvp"], cache["ckvs"], bt)
                kpe_all = _paged_gather_deq(cache["kpep"], cache["kpes"], bt)
        else:
            with jax.named_scope("kv_write"):
                cache = {
                    "ckvp": _paged_write(cache["ckvp"], ckv, bt, positions),
                    "kpep": _paged_write(cache["kpep"], kpe, bt, positions),
                }
            if not use_kernel:
                ckv_all = _paged_gather(cache["ckvp"], bt)
                kpe_all = _paged_gather(cache["kpep"], bt)
        if use_kernel:
            ckv_all = kpe_all = kpos = None
        else:
            kpos = _paged_kpos(positions, ckv_all.shape[1])
    elif cache is not None:
        cache = _write_cache(cache, {"ckv": ckv, "kpe": kpe}, positions[:, 0], ring=False)
        ckv_all, kpe_all, kpos = cache["ckv"], cache["kpe"], cache["kpos"]
    else:
        ckv_all, kpe_all = ckv, kpe
        kpos = jnp.broadcast_to(positions, (B, T))

    wkv_b = params["wkv_b"]
    if absorb and cache is not None:
        # Beyond-paper decode optimization: fold wkv_b into the query/output
        # so scores are taken directly against the compressed latent cache.
        # Numerically identical to the materialized path (incl. the activation
        # quantizer, applied to the latent exactly as lin(wkv_b, .) would).
        w_full = _mla_up_matrix(wkv_b, a, q)  # (kv_lora, H, nope+vd)
        has_aq = q.mode != "none" and "aq" in wkv_b
        w_k, w_v = w_full[..., :nope], w_full[..., nope:]
        q_lat = jnp.einsum("bthn,lhn->bthl", q_nope.astype(jnp.float32), w_k.astype(jnp.float32))
        scale = (nope + rope) ** -0.5
        if use_kernel:
            from repro.kernels import ops

            aq_scale = None
            if has_aq:
                aq_scale = jnp.exp2(wkv_b["aq"]["log2_scale"].astype(jnp.float32))
            o_lat = ops.paged_mla_attention(
                q_lat[:, 0], q_pe[:, 0].astype(jnp.float32),
                cache["ckvp"], cache["kpep"], bt, positions[:, 0] + 1,
                ckvs=cache.get("ckvs"), kpes=cache.get("kpes"), scale=scale,
                aq_scale=aq_scale,
                act_bits=q.act_bits if aq_scale is not None else None,
            )[:, None]
        else:
            if has_aq:
                from repro.core.quantizers import apply_act_quant

                ckv_all = apply_act_quant(
                    {"log2_scale": wkv_b["aq"]["log2_scale"]}, ckv_all, q.act_bits, signed=True
                )
            s = jnp.einsum("bthl,bsl->bths", q_lat, ckv_all.astype(jnp.float32))
            s += jnp.einsum("bthr,bsr->bths", q_pe.astype(jnp.float32), kpe_all.astype(jnp.float32))
            s *= scale
            qp = positions[:, :, None]
            kp = kpos[:, None, :]
            mask = (kp >= 0) & (kp <= qp)
            s = jnp.where(mask[:, :, None, :], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bths,bsl->bthl", p, ckv_all.astype(jnp.float32))
        out = jnp.einsum("bthl,lhv->bthv", o_lat, w_v.astype(jnp.float32))
        out = out.astype(compute_dtype).reshape(B, T, H * vd)
        return lin(params["wo"], x=out, site="mla.wo"), cache

    # Materialized path (paper-faithful baseline): expand per-head K/V.
    S = ckv_all.shape[1]
    kv = lin(wkv_b, x=ckv_all, site="mla.wkv_b").reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe_all[:, :, None, :], (B, S, H, rope))], axis=-1
    )
    qfull = jnp.concatenate([q_nope, q_pe], axis=-1)
    out = _sdpa(
        qfull, k, v, positions, kpos,
        causal=a.causal, window=None, chunk=None, q_chunk=q_chunk,
    )
    out = out.reshape(B, T, H * vd)
    return lin(params["wo"], x=out, site="mla.wo"), cache


def _mla_up_matrix(wkv_b_params: dict, a: AttnConfig, q: QuantConfig) -> jnp.ndarray:
    from repro.nn.linear import _quant_weights  # quantized view of the up-proj

    w = _quant_weights(wkv_b_params, q, boundary=False, input_signed=True)
    kv_lora = w.shape[0]
    return w.reshape(kv_lora, a.heads, a.qk_nope_dim + a.v_head_dim)


def attention_penalty(params: dict, a: AttnConfig, q: QuantConfig) -> jnp.ndarray:
    """Sum of A2Q regularizer terms over this layer's projections."""
    total = jnp.zeros((), jnp.float32)
    for name, sub in params.items():
        if isinstance(sub, dict) and ("t" in sub or "w" in sub or "v" in sub):
            total = total + linear_penalty(sub, q, boundary=False, input_signed=True)
    return total
