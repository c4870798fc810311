"""Pallas TPU kernel: paged-attention decode over block-table KV pools.

One query token per sequence attends to K/V scattered across fixed-size token
pages (``serve/paged_cache.py`` owns the layout): pool ``(NB, bs, KV, Dh)``,
per-sequence block table ``bt (B, MB)``, per-sequence length.  The
``(B, MB * bs, ...)`` contiguous view is never materialized (the jnp twin
``ref.ref_paged_attention`` materializes it; `ops.py` picks).

The walk.  Grid step ``(b, i)`` computes *compute block* ``i`` of row
``b``: ``pages`` consecutive table columns (``compute_block_pages``, about
256 tokens, from the page size, the pool's width and dtype and the table's
width).  Each page slot is its own operand, so the pipeline DMAs the next
step's pages into VMEM while this step computes, the next row's first block
included; its index map reads the pool page from a scalar-prefetched walk
table (``_walk_pages``, made once a call from ``bt`` and the lengths).  A
row of length ``L`` walks only the blocks ``kv_block_range`` gives: the
steps past them (and, with ``window``, those wholly before it) name the
nearest walked block again, so they issue no DMA and skip their compute.
Inside the last live block, the columns past the row's last live page name
that page again: a dead table entry is never read.  The engine counts the
walked blocks with the same ``kv_block_range`` (``decode_megastep`` span).
The pages come through the pipeline, not through manual DMAs from pools
left in HBM, because Mosaic refuses a DMA slice of the fp32 scale pools
(the page's ``KV`` lanes pad to 128); on a v5e the two cost about the same
a page, and what made a step cheap was its one-read index maps.

Softmax is the same fp32 online (running max / sum / accumulator) scheme as
``flash_attention.py``.  A page keeps all of its KV heads (the ``(KV, Dh)``
trailing dims stay whole, as the TPU block-tiling rule requires) and the
kernel loops over the heads; GQA puts the ``G = H // KV`` query group in
the row dim of each head's score panel.  Key validity comes from the per-row
length: position ``i * pages * bs + o`` participates iff it is ``< length``
— dead rows (length 0) produce a zero output via the flush-time denominator
guard, never a NaN.

Quantized pools (the int8 KV-cache serve path): with ``kps``/``vps`` — one
fp32 scale per block-slot per KV head, stored in the same ``(NB, bs, KV)``
block layout and fetched by the same walk — the K/V operands are
int8 and the kernel dequantizes *in register* inside the online-softmax loop:
the int8 block is what DMAs from HBM (~4x less decode bandwidth than fp32),
the fp32 view never exists outside VMEM.  Oracle:
``ref.ref_paged_attention_q8``.

Packed int4 pools (uint8, two codes per byte, half the feature width) ride
the same scale machinery: the kernel detects the byte-width from the pool
dtype, DMAs the nibble-packed block, and unpacks + sign-extends in register
before the per-slot rescale — ~8x less decode bandwidth than fp32.  It
works in a split feature order (even features, then odd: no lane
interleave), taking its queries and giving its outputs in that order.
Oracle: ``ref.ref_paged_attention_q4``.

``paged_mla_attention_*`` is the latent-attention sibling for MLA absorbed
decode: scores are taken directly against the compressed ``(ckv, kpe)``
latent pools (rank R + rope P per token instead of H heads x Dh), the PV
accumulation reuses the *same* ckv block, and the per-head up-projections
stay outside the kernel.  Supports fp32 / int8 / packed-int4 latent pools
and an optional in-kernel activation fake-quant of the dequantized latent
(`clip(round(x/s)) * s`) so the absorbed-decode numerics — including the
A2Q activation quantizer the absorb path folds in — match the gathered
oracle ``ref.ref_paged_mla_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "compute_block_pages",
    "kv_block_range",
    "paged_attention_kernel",
    "paged_attention_pallas",
    "paged_mla_attention_kernel",
    "paged_mla_attention_pallas",
]

_NEG_INF = -1e30


def _unpack_nibbles_split_f32(x: jnp.ndarray) -> jnp.ndarray:
    """Packed codes widened to int32 ``(T, D // 2)`` -> fp32 ``(T, D)`` in
    *split* feature order: the low nibbles (even features) then the high
    (odd).  No lane interleave; the GQA kernel takes its queries and gives
    its outputs in the same order."""
    se = lambda x: (x ^ 8) - 8
    return jnp.concatenate([se(x & 0xF), se((x >> 4) & 0xF)], axis=-1).astype(jnp.float32)


def _unpack_nibbles_f32(u: jnp.ndarray) -> jnp.ndarray:
    """Packed uint8 ``(bs, D // 2)`` -> fp32 codes ``(bs, D)`` (element 2i in
    the low nibble, 2i+1 in the high; ``(x ^ 8) - 8`` sign extension) —
    in-register twin of the layer-side ``_unpack_nibbles``."""
    x = u.astype(jnp.int32)  # Mosaic has no 8-bit shifts; widen first
    lo = x & 0xF
    hi = (x >> 4) & 0xF
    se = lambda x: (x ^ 8) - 8
    codes = jnp.stack([se(lo), se(hi)], axis=-1)
    return codes.reshape(u.shape[0], u.shape[1] * 2).astype(jnp.float32)


# A compute block covers ``pages`` table columns: about this many tokens a
# grid step, with the K/V double buffers inside this much VMEM (well under
# v5e's 16 MiB default scoped limit, counted with the (sublane, 128-lane)
# tile padding the buffers take there).
_BLOCK_TOKENS = 256
_BUFFER_VMEM = 6 << 20


def _padded_page_bytes(block_size: int, rows: int, width: int, dtype) -> int:
    """VMEM bytes of one ``(block_size, rows, width)`` page: the last two
    dims pad to a tile of ``32 // itemsize`` sublanes by 128 lanes."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 32 // itemsize
    return block_size * (-(-rows // sub) * sub) * (-(-width // 128) * 128) * itemsize


def compute_block_pages(
    block_size: int, kv_heads: int, width: int, dtype, max_blocks: int, quantized: bool
) -> int:
    """Pages (table columns) per compute block of the GQA decode kernel for a
    pool ``(NB, block_size, kv_heads, width)`` of ``dtype`` and a table of
    ``max_blocks`` columns: ~``_BLOCK_TOKENS`` tokens a step, K and V double
    buffered (with their scale pages when ``quantized``) within
    ``_BUFFER_VMEM``, never more than the table holds."""
    page = _padded_page_bytes(block_size, kv_heads, width, dtype)
    if quantized:
        page += _padded_page_bytes(1, block_size, kv_heads, jnp.float32)
    by_vmem = _BUFFER_VMEM // (4 * page)  # K and V, two buffers each
    return max(1, min(max_blocks, _BLOCK_TOKENS // block_size, by_vmem))


def kv_block_range(length, block_tokens: int, window: Optional[int] = None):
    """``(first, end)``: the compute blocks of ``block_tokens`` keys that a
    row of ``length`` tokens walks, ``first <= i < end`` — the blocks that
    hold a key at ``kpos < length`` (and, with ``window``, at
    ``kpos >= length - window``).  A zero-length row walks none.  Plain
    integer arithmetic, so it takes Python ints, numpy arrays (the engine's
    count of walked blocks) and the kernel's traced lengths (its loop
    bounds) alike."""
    end = (length + block_tokens - 1) // block_tokens
    if window is None:
        return end * 0, end
    lo = length - window
    return (lo * (lo > 0)) // block_tokens, end


def _walk_pages(bt, lengths, *, block_size: int, pages: int, n_blocks: int,
                window: Optional[int]) -> jnp.ndarray:
    """``(B, n_blocks * pages)``: the pool page that page slot ``p`` of grid
    step ``(b, i)`` names, at ``[b, i * pages + p]``; made once a call, so
    that each operand's index map is one table read.

    A step outside the row's walk ``[first, end)`` names the pages of the
    nearest walked block, so the pipeline, finding the block unchanged,
    issues no DMA for it.  Columns past the row's last live page name that
    page again (finite, and fully masked there), so a dead table entry is
    never read."""
    MB = bt.shape[1]
    first, end = kv_block_range(lengths, pages * block_size, window)
    i = jnp.arange(n_blocks)[None, :]
    i = jnp.maximum(jnp.minimum(i, end[:, None] - 1), first[:, None])  # (B, n_blocks)
    last = jnp.minimum((lengths + block_size - 1) // block_size - 1, MB - 1)
    col = i[:, :, None] * pages + jnp.arange(pages)  # (B, n_blocks, pages)
    col = jnp.maximum(jnp.minimum(col, last[:, None, None]), 0)
    return jnp.take_along_axis(bt, col.reshape(bt.shape[0], -1), axis=1)


def paged_attention_kernel(
    pages_ref,  # (B, n_blocks * pages) scalar-prefetch walk table (``_walk_pages``)
    len_ref,  # (B,)   scalar-prefetch per-row lengths
    q_ref,  # (1, KV, G, Dh)
    *refs,  # pages x K (1, bs, KV, Dhp), pages x V, [pages x K scale (1, bs, KV), pages x V scale],
    #         o_ref, m, l, acc
    scale: float,
    block_size: int,
    pages: int,
    kv_heads: int,
    quantized: bool,
    packed: bool = False,
    window: Optional[int] = None,
):
    k_refs, v_refs = refs[:pages], refs[pages : 2 * pages]
    if quantized:
        ks_refs, vs_refs = refs[2 * pages : 3 * pages], refs[3 * pages : 4 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]  # scratch (KV, G, 1) x2, (KV, G, Dh)
    b = pl.program_id(0)
    i = pl.program_id(1)
    tokens = pages * block_size

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    first, end = kv_block_range(length, tokens, window)

    cat = functools.partial(jnp.concatenate, axis=0)  # the block's pages, in order

    def load(page_refs, scale_refs, h):
        """Head h's keys or values of the compute block, ``(tokens, Dh)``
        fp32, dequantized in register when the pools are integer."""
        if packed:
            # widen before joining the pages: Mosaic has no 8-bit shifts
            x = _unpack_nibbles_split_f32(cat([r[0, :, h, :].astype(jnp.int32) for r in page_refs]))
        else:
            x = cat([r[0, :, h, :].astype(jnp.float32) for r in page_refs])
        if quantized:
            # the fp32 view exists only in VMEM
            x = x * cat([r[0, :, h][:, None] for r in scale_refs])
        return x

    @pl.when((i >= first) & (i < end))
    def _block():
        kpos = i * tokens + jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
        valid = kpos < length
        if window is not None:
            # the single decode query sits at position length - 1; a sliding
            # window admits keys in (length - 1 - window, length - 1], i.e.
            # kpos >= length - window
            valid &= kpos >= length - window
        # each KV head's G-query panel runs its own online-softmax update
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, Dh)
            k = load(k_refs, ks_refs if quantized else None, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (G, tokens)
            s = jnp.where(valid, s, _NEG_INF)

            m_prev = m_ref[h]  # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[h] = m_new
            v = load(v_refs, vs_refs if quantized else None, h)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            acc_ref[h] = alpha * acc_ref[h] + pv

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        l = l_ref[...]
        norm = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = (acc_ref[...] * norm).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,  # (B, KV, G, Dh)
    kp: jnp.ndarray,  # (NB, bs, KV, Dh)
    vp: jnp.ndarray,  # (NB, bs, KV, Dh)
    bt: jnp.ndarray,  # (B, MB) int32
    lengths: jnp.ndarray,  # (B,) int32
    kps: Optional[jnp.ndarray] = None,  # (NB, bs, KV) fp32 — int8 pool scales
    vps: Optional[jnp.ndarray] = None,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns ``(B, KV, G, Dh)`` attention outputs for one decode token per
    row.  ``lengths`` counts valid tokens (including this step's freshly
    written one), at most ``MB * bs``; table entries past a row's length may
    point anywhere — they are never read.  ``kps``/``vps`` given =>
    ``kp``/``vp`` are int8 pools dequantized in-kernel against the per-slot
    scales.  ``window`` masks to the sliding window ending at the query
    position (keys at ``kpos >= length - window``) and skips the compute
    blocks wholly before it — the windowed-decode coverage for
    ring/sliding-window archs.  uint8 pools are the nibble-packed int4
    layout (feature width ``Dh // 2``) and are unpacked in register."""
    B, KV, G, Dh = q.shape
    NB, bs, _, Dhp = kp.shape
    MB = bt.shape[1]
    quantized = kps is not None
    packed = kp.dtype == jnp.uint8
    if packed and not quantized:
        raise ValueError("packed int4 pools need kps/vps scale pools")
    if scale is None:
        scale = Dh**-0.5
    pages = compute_block_pages(bs, KV, Dhp, kp.dtype, MB, quantized)
    n_blocks = -(-MB // pages)

    kernel = functools.partial(
        paged_attention_kernel, scale=scale, block_size=bs, pages=pages,
        kv_heads=KV, quantized=quantized, packed=packed, window=window,
    )
    lengths = lengths.astype(jnp.int32)
    walk = _walk_pages(bt.astype(jnp.int32), lengths, block_size=bs, pages=pages,
                       n_blocks=n_blocks, window=window)

    def page_spec(block, p):
        rest = (0,) * (len(block) - 1)
        return pl.BlockSpec(block, lambda b, i, pg_ref, len_ref: (pg_ref[b, i * pages + p], *rest))

    row_spec = pl.BlockSpec((1, KV, G, Dh), lambda b, i, pg_ref, len_ref: (b, 0, 0, 0))
    code_specs = [page_spec((1, bs, KV, Dhp), p) for p in range(pages)]
    in_specs = [row_spec] + code_specs * 2
    if packed:  # queries in the split feature order of the unpacked codes
        q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)
    operands = [q] + [kp] * pages + [vp] * pages
    if quantized:
        in_specs += [page_spec((1, bs, KV), p) for p in range(pages)] * 2
        operands += [kps.astype(jnp.float32)] * pages + [vps.astype(jnp.float32)] * pages
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(walk, lengths, *operands)
    if packed:  # back from the split feature order
        out = out.reshape(B, KV, G, 2, Dh // 2).swapaxes(-1, -2).reshape(B, KV, G, Dh)
    return out


# ---------------------------------------------------------------------------
# MLA latent attention: absorbed decode directly over the compressed pools.
# ---------------------------------------------------------------------------


def paged_mla_attention_kernel(
    bt_ref,  # (B, MB) scalar-prefetch block table
    len_ref,  # (B,)   scalar-prefetch per-row lengths
    ql_ref,  # (1, H, R)  absorbed query in latent space
    qp_ref,  # (1, H, P)  rope query half
    ckv_ref,  # (1, bs, R) latent block bt[b, j]; int8 / packed uint8 when quantized
    kpe_ref,  # (1, bs, P) rope-key block
    *rest,  # [ckvs_ref, kpes_ref][, aq_ref], o_ref, m, l, acc
    scale: float,
    block_size: int,
    mb_steps: int,
    quantized: bool,
    packed: bool,
    act_bits: Optional[int],
):
    idx = 0
    if quantized:
        ckvs_ref, kpes_ref = rest[idx], rest[idx + 1]  # (1, bs, 1) fp32 per-token scales
        idx += 2
    if act_bits is not None:
        aq_ref = rest[idx]  # (1, 1) fp32 activation-quantizer scale
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[0].astype(jnp.float32)  # (H, R)
    qp = qp_ref[0].astype(jnp.float32)  # (H, P)
    if packed:
        ckv = _unpack_nibbles_f32(ckv_ref[0])  # (bs, R)
        kpe = _unpack_nibbles_f32(kpe_ref[0])  # (bs, P)
    else:
        ckv = ckv_ref[0].astype(jnp.float32)
        kpe = kpe_ref[0].astype(jnp.float32)
    if quantized:
        ckv = ckv * ckvs_ref[0]
        kpe = kpe * kpes_ref[0]
    if act_bits is not None:
        # The absorb path runs the latent through the up-projection's A2Q
        # activation quantizer; replay the fake-quant on the dequantized
        # block so score *and* PV see exactly the quantized latent.
        n = -(1 << (act_bits - 1))
        p_max = (1 << (act_bits - 1)) - 1
        s_aq = aq_ref[0, 0]
        ckv = jnp.clip(jnp.round(ckv / s_aq), n, p_max) * s_aq

    s = jax.lax.dot_general(
        ql, ckv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (H, bs)
    s += jax.lax.dot_general(
        qp, kpe, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s *= scale

    length = len_ref[b]
    kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    valid = kpos < length
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...]  # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (H, R) — PV reuses the same (dequantized, act-quantized) latent block
    acc_ref[...] = alpha * acc_ref[...] + pv

    @pl.when(j == mb_steps - 1)
    def _flush():
        l = l_ref[...]
        norm = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = (acc_ref[...] * norm).astype(o_ref.dtype)


def paged_mla_attention_pallas(
    q_lat: jnp.ndarray,  # (B, H, R) — q_nope absorbed through w_k
    q_pe: jnp.ndarray,  # (B, H, P)
    ckvp: jnp.ndarray,  # (NB, bs, R) latent pool (fp / int8 / packed uint8)
    kpep: jnp.ndarray,  # (NB, bs, P) rope-key pool
    bt: jnp.ndarray,  # (B, MB) int32
    lengths: jnp.ndarray,  # (B,) int32
    ckvs: Optional[jnp.ndarray] = None,  # (NB, bs) fp32 latent scales
    kpes: Optional[jnp.ndarray] = None,
    *,
    scale: float,
    aq_scale: Optional[jnp.ndarray] = None,  # scalar activation-quant scale
    act_bits: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns ``(B, H, R)`` latent attention outputs (``o_lat``; the caller
    up-projects through ``w_v``).  ``scale`` is the absorbed score scale
    ``(qk_nope_dim + qk_rope_dim) ** -0.5`` — not derivable from the latent
    shapes, so it is required.  ``aq_scale``/``act_bits`` replay the A2Q
    activation fake-quant on the dequantized latent in register (``aq_scale``
    is a traced scalar, shipped as a ``(1, 1)`` operand)."""
    B, H, R = q_lat.shape
    P = q_pe.shape[-1]
    NB, bs = ckvp.shape[:2]
    MB = bt.shape[1]
    quantized = ckvs is not None
    packed = ckvp.dtype == jnp.uint8
    if packed and not quantized:
        raise ValueError("packed int4 latent pools need ckvs/kpes scale pools")
    if (act_bits is None) != (aq_scale is None):
        raise ValueError("aq_scale and act_bits must be given together")

    kernel = functools.partial(
        paged_mla_attention_kernel, scale=scale, block_size=bs, mb_steps=MB,
        quantized=quantized, packed=packed, act_bits=act_bits,
    )
    in_specs = [
        pl.BlockSpec((1, H, R), lambda b, j, bt_ref, len_ref: (b, 0, 0)),
        pl.BlockSpec((1, H, P), lambda b, j, bt_ref, len_ref: (b, 0, 0)),
        pl.BlockSpec((1, bs, ckvp.shape[-1]),
                     lambda b, j, bt_ref, len_ref: (bt_ref[b, j], 0, 0)),
        pl.BlockSpec((1, bs, kpep.shape[-1]),
                     lambda b, j, bt_ref, len_ref: (bt_ref[b, j], 0, 0)),
    ]
    operands = [q_lat, q_pe, ckvp, kpep]
    if quantized:
        # a trailing unit axis makes the block's last two dims (bs, 1):
        # bs a multiple of 8 and 1 the whole axis, as TPU tiling requires
        scale_spec = pl.BlockSpec(
            (1, bs, 1), lambda b, j, bt_ref, len_ref: (bt_ref[b, j], 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [ckvs.astype(jnp.float32)[..., None],
                     kpes.astype(jnp.float32)[..., None]]
    if act_bits is not None:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, j, bt_ref, len_ref: (0, 0)))
        operands.append(jnp.asarray(aq_scale, jnp.float32).reshape(1, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, R), lambda b, j, bt_ref, len_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        interpret=interpret,
        name="paged_mla_attention",
    )(bt.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
