"""Pallas TPU kernel: paged-attention decode over block-table KV pools.

One query token per sequence attends to K/V scattered across fixed-size token
blocks (``serve/paged_cache.py`` owns the layout): pool ``(NB, bs, KV, Dh)``,
per-sequence block table ``bt (B, MB)``, per-sequence length.  The kernel
walks each row's table with the KV-block axis innermost and *gathers through
the table at the BlockSpec level*: the block table is a scalar-prefetch
operand (``pltpu.PrefetchScalarGridSpec``), so the index map of the K/V
operands reads ``bt[b, j]`` to pick which pool block the next grid step DMAs
into VMEM — the ``(B, MB * bs, ...)`` contiguous view is never materialized
(the jnp twin ``ref.ref_paged_attention`` materializes it; `ops.py` picks).

Softmax is the same fp32 online (running max / sum / accumulator) scheme as
``flash_attention.py``.  Each grid step ``(b, j)`` loads one pool block
with all of its KV heads (the ``(KV, Dh)`` trailing dims stay whole, as the
TPU block-tiling rule requires) and loops over the heads in the kernel; GQA
puts the ``G = H // KV`` query group in the row dim of each head's score
panel.  Key validity
comes from the per-row length: position ``j * bs + o`` participates iff it is
``< length`` — dead rows (length 0) produce a zero output via the flush-time
denominator guard, never a NaN.

Quantized pools (the int8 KV-cache serve path): with ``kps``/``vps`` — one
fp32 scale per block-slot per KV head, stored in the same ``(NB, bs, KV)``
block layout and gathered through the same table entry — the K/V operands are
int8 and the kernel dequantizes *in register* inside the online-softmax loop:
the int8 block is what DMAs from HBM (~4x less decode bandwidth than fp32),
the fp32 view never exists outside VMEM.  Oracle:
``ref.ref_paged_attention_q8``.

Packed int4 pools (uint8, two codes per byte, half the feature width) ride
the same scale machinery: the kernel detects the byte-width from the pool
dtype, DMAs the nibble-packed block, and unpacks + sign-extends in register
before the per-slot rescale — ~8x less decode bandwidth than fp32.  Oracle:
``ref.ref_paged_attention_q4``.

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
    "paged_attention_kernel",
    "paged_attention_pallas",
    "paged_mla_attention_kernel",
    "paged_mla_attention_pallas",
]

_NEG_INF = -1e30


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


def paged_attention_kernel(
    bt_ref,  # (B, MB) scalar-prefetch block table
    len_ref,  # (B,)   scalar-prefetch per-row lengths
    q_ref,  # (1, KV, G, Dh)
    k_ref,  # (1, bs, KV, Dh) — every KV head of pool block bt[b, j]; int8 when quantized
    v_ref,  # (1, bs, KV, Dh)
    *rest,  # quantized: (ks_ref, vs_ref, o_ref, scratch...) else (o_ref, ...)
    scale: float,
    block_size: int,
    mb_steps: int,
    kv_heads: int,
    quantized: bool,
    packed: bool = False,
    window: Optional[int] = None,
):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]  # (1, bs, KV) fp32 per-slot scales
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]  # scratch (KV, G, 1) x2, (KV, G, Dh)
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
    valid = kpos < length
    if window is not None:
        # the single decode query sits at position length - 1; a sliding
        # window admits keys in (length - 1 - window, length - 1], i.e.
        # kpos >= length - window
        valid &= kpos >= length - window

    def load(ref, h):
        x = ref[0, :, h, :]  # (bs, Dh), or (bs, Dh // 2) packed
        return _unpack_nibbles_f32(x) if packed else x.astype(jnp.float32)

    # the block holds every KV head (the pool's trailing (KV, Dh) dims are
    # whole, which is what the TPU tiling rule asks of a block); each head's
    # G-query panel runs its own online-softmax update
    for h in range(kv_heads):
        q = q_ref[0, h].astype(jnp.float32) * scale  # (G, Dh)
        k = load(k_ref, h)
        if quantized:
            # in-register dequant: the fp32 K block exists only in VMEM
            k = k * ks_ref[0, :, h][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bs)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[h]  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        v = load(v_ref, h)
        if quantized:
            v = v * vs_ref[0, :, h][:, None]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[h] = alpha * acc_ref[h] + pv

    @pl.when(j == mb_steps - 1)
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
    written one); table entries past a row's length may point anywhere — they
    are loaded and fully masked.  ``kps``/``vps`` given => ``kp``/``vp`` are
    int8 pools dequantized in-kernel against the per-slot scales.
    ``window`` masks to the sliding window ending at the query position
    (keys at ``kpos >= length - window``) — the windowed-decode coverage for
    ring/sliding-window archs.  uint8 pools are the nibble-packed int4 layout
    (feature width ``Dh // 2``) and are unpacked in register."""
    B, KV, G, Dh = q.shape
    NB, bs, _, Dhp = kp.shape
    MB = bt.shape[1]
    quantized = kps is not None
    packed = kp.dtype == jnp.uint8
    if packed and not quantized:
        raise ValueError("packed int4 pools need kps/vps scale pools")
    if scale is None:
        scale = Dh**-0.5

    kernel = functools.partial(
        paged_attention_kernel, scale=scale, block_size=bs, mb_steps=MB,
        kv_heads=KV, quantized=quantized, packed=packed, window=window,
    )
    pool_spec = pl.BlockSpec(
        (1, bs, KV, Dhp), lambda b, j, bt_ref, len_ref: (bt_ref[b, j], 0, 0, 0)
    )
    row_spec = pl.BlockSpec((1, KV, G, Dh), lambda b, j, bt_ref, len_ref: (b, 0, 0, 0))
    in_specs = [row_spec, pool_spec, pool_spec]
    operands = [q, kp, vp]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, bs, KV), lambda b, j, bt_ref, len_ref: (bt_ref[b, j], 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [kps.astype(jnp.float32), vps.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(bt.astype(jnp.int32), lengths.astype(jnp.int32), *operands)


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
