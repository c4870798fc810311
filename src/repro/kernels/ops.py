"""Public jit'd wrappers around the Pallas kernels.

Each op handles padding to block multiples, head folding, dtype plumbing, and
an ``interpret`` default (True off-TPU so the kernels execute via the Pallas
interpreter on CPU; on TPU they compile to Mosaic).  Layers call these — never
``pallas_call`` directly — and every op has a pure-jnp oracle in ``ref.py``
that the test suite sweeps against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.a2q_quantize import a2q_quantize_pallas
from repro.kernels.causal_attention import causal_attention_pallas
from repro.kernels.int_matmul import int_matmul_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_mla_attention_pallas,
)
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas

__all__ = [
    "int_matmul",
    "a2q_quantize",
    "causal_attention",
    "paged_attention",
    "paged_mla_attention",
    "rwkv6_scan",
]


def _default_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x: jnp.ndarray, axis: int, to: int, value=0):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def int_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    acc_bits: int = 32,
    mode: str = "exact",
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    out_scale: Optional[jnp.ndarray] = None,
    out_bits: int = 8,
    out_signed: bool = True,
    act_fn: Optional[str] = None,
    cast_dtype=jnp.float32,
    aq_scale: Optional[jnp.ndarray] = None,
    in_bits: int = 8,
    in_signed: bool = True,
    out_dtype=jnp.float32,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    spill_int16: bool = False,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """int8 x int8 -> int32 matmul ``(M, K) @ (K, N)`` with P-bit accumulator
    emulation.  Zero padding is sound for all modes (adding zero then wrapping
    or saturating an in-range value is the identity).

    ``scale`` (scalar or per-column ``(N,)`` fp32 — e.g. the deployed layer's
    ``s8`` with the activation scale folded in) engages the fused epilogue:
    the int32 accumulator is rescaled (+ ``bias``) in VMEM and the op returns
    ``out_dtype`` instead of raw int32.  Oracle: ``ref.ref_int_matmul_fused``.

    Int8-out chaining (oracle: ``ref.ref_int_matmul_requant``):

    * ``in_signed=False, in_bits=8`` declares that ``x`` carries *symmetrized*
      unsigned codes (``true_code - 128`` as int8, or the fp32 prologue input
      of an unsigned consumer); the wrapper adds the exact correction
      ``128 * colsum(w)`` to the accumulator at flush, so unsigned-activation
      layers ride the fused path at full ``N=8``.
    * ``out_scale`` (scalar or per-column ``(N,)`` fp32 — the *next* layer's
      activation scale) engages the requantizing epilogue: the rescaled
      accumulator is passed through ``act_fn`` (``None``/``'relu2'``/
      ``'gelu'``, replayed in ``cast_dtype`` exactly as the layer code
      computes it) and re-quantized to int8 codes for ``out_bits``/
      ``out_signed`` in the same flush — the op returns int8, and unsigned
      targets come out symmetrized.
    * ``aq_scale`` (scalar fp32) engages the quantizing prologue: ``x``
      arrives fp32 and each tile is quantized in-register before the dot —
      the chain-break entry point needs no standalone act-quant dispatch.
    """
    M, K = x.shape
    _, N = w.shape
    bm = min(block_m, _round_up(M, 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 128))
    Np = _round_up(N, bn)
    Kp = _round_up(K, bk)
    xp = _pad_axis(_pad_axis(x, 0, _round_up(M, bm)), 1, Kp)
    wp = _pad_axis(_pad_axis(w, 0, Kp), 1, Np)
    if scale is not None:
        scale = _pad_axis(
            jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (N,)).reshape(1, N), 1, Np
        )
    if bias is not None:
        if scale is None:
            raise ValueError("int_matmul: bias requires an epilogue scale")
        bias = _pad_axis(jnp.asarray(bias, jnp.float32).reshape(1, N), 1, Np)
    if not in_signed and in_bits == 8:
        # symmetrized unsigned operand: q = qs + 128, so
        # acc_true = acc_sym + 128 * colsum(w).  Exact in int32; w's K padding
        # is zeros, so the unpadded colsum is already correct.
        sym = 128 * jnp.sum(w.astype(jnp.int32), axis=0)
        offset = sym if offset is None else jnp.asarray(offset, jnp.int32) + sym
    if offset is not None:
        if scale is None:
            raise ValueError("int_matmul: offset requires an epilogue scale")
        offset = _pad_axis(jnp.asarray(offset, jnp.int32).reshape(1, N), 1, Np)
    if out_scale is not None:
        if scale is None:
            raise ValueError("int_matmul: out_scale requires an epilogue scale")
        # pad columns divide by 1 (never 0) and are sliced off below
        out_scale = _pad_axis(
            jnp.broadcast_to(jnp.asarray(out_scale, jnp.float32), (N,)).reshape(1, N),
            1, Np, value=1,
        )
    if aq_scale is not None:
        if scale is None:
            raise ValueError("int_matmul: aq_scale requires an epilogue scale")
        aq_scale = _pad_axis(
            jnp.broadcast_to(jnp.asarray(aq_scale, jnp.float32), (K,)).reshape(1, K),
            1, Kp, value=1,
        )
    out = int_matmul_pallas(
        xp,
        wp,
        scale,
        bias,
        offset,
        out_scale,
        aq_scale,
        acc_bits=acc_bits,
        mode=mode,
        block_m=bm,
        block_n=bn,
        block_k=bk,
        spill_dtype=jnp.int16 if spill_int16 else jnp.int32,
        out_dtype=out_dtype,
        out_bits=out_bits,
        out_signed=out_signed,
        act_fn=act_fn,
        cast_dtype=cast_dtype,
        in_bits=in_bits,
        in_signed=in_signed,
        interpret=_default_interpret(interpret),
    )
    return out[:M, :N]


def a2q_quantize(
    v: jnp.ndarray,
    t: jnp.ndarray,
    d: jnp.ndarray,
    *,
    weight_bits: int,
    acc_bits: int,
    input_bits: int,
    input_signed: bool,
    block_k: int = 512,
    block_c: int = 256,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused A2Q quantizer for a ``(K, C)`` weight matrix with per-channel
    ``t``/``d`` of shape ``(C,)``.  Returns (dequantized fp32, int8 weights).

    K padding uses v=0 (adds nothing to the l1 norm); C padding uses t=d=0
    (garbage channels sliced off).
    """
    K, C = v.shape
    bk = min(block_k, _round_up(K, 8))
    bc = min(block_c, _round_up(C, 128))
    Kp, Cp = _round_up(K, bk), _round_up(C, bc)
    vp = _pad_axis(_pad_axis(v, 0, Kp), 1, Cp)
    tp = _pad_axis(t.reshape(1, C), 1, Cp)
    dp = _pad_axis(d.reshape(1, C), 1, Cp)
    deq, q = a2q_quantize_pallas(
        vp,
        tp,
        dp,
        weight_bits=weight_bits,
        acc_bits=acc_bits,
        input_bits=input_bits,
        input_signed=input_signed,
        block_k=bk,
        block_c=bc,
        interpret=_default_interpret(interpret),
    )
    return deq[:K, :C], q[:K, :C]


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal grouped-query attention over ``(B, T, H, Dh)`` queries and
    ``(B, T, KV, Dh)`` keys/values, positions ``0 .. T-1`` on every row, with
    a backward (``kernels/causal_attention.py``).  ``T`` must be a multiple
    of ``causal_attention.block_size(T)``; oracle: the attention layer's
    ``_sdpa``."""
    return causal_attention_pallas(q, k, v, interpret=_default_interpret(interpret))


def paged_attention(
    q: jnp.ndarray,
    kp: jnp.ndarray,
    vp: jnp.ndarray,
    bt: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    kps: Optional[jnp.ndarray] = None,
    vps: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paged-attention decode: one query token per row against block-table
    K/V pools.  ``q (B, H, Dh)``, pools ``(NB, bs, KV, Dh)``, table
    ``bt (B, MB)``, ``lengths (B,)`` counting valid tokens (including this
    step's write).  Returns ``(B, H, Dh)``.  Oracle:
    ``ref.ref_paged_attention``.

    ``kps``/``vps`` (``(NB, bs, KV)`` fp32): the pools are integer and the
    kernel dequantizes in-register — int8 codes directly (oracle:
    ``ref.ref_paged_attention_q8``) or, when the pools are uint8, the packed
    int4 layout at feature width ``Dh // 2``, unpacked + sign-extended in
    register (oracle: ``ref.ref_paged_attention_q4``).

    ``window``: sliding-window masking — each row attends keys at
    ``kpos >= length - window`` only (windowed-decode kernel coverage).
    """
    B, H, Dh = q.shape
    KV = kp.shape[2]
    G = H // KV
    if (kps is None) != (vps is None):
        raise ValueError("paged_attention: kps and vps must be given together")
    if kp.dtype == jnp.uint8 and kps is None:
        raise ValueError("paged_attention: packed int4 pools need kps/vps")
    if window is not None and window < 1:
        raise ValueError("paged_attention: window must be >= 1")
    out = paged_attention_pallas(
        q.reshape(B, KV, G, Dh),
        kp,
        vp,
        bt,
        lengths,
        kps,
        vps,
        scale=scale,
        window=window,
        interpret=_default_interpret(interpret),
    )
    return out.reshape(B, H, Dh)


def paged_mla_attention(
    q_lat: jnp.ndarray,
    q_pe: jnp.ndarray,
    ckvp: jnp.ndarray,
    kpep: jnp.ndarray,
    bt: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    ckvs: Optional[jnp.ndarray] = None,
    kpes: Optional[jnp.ndarray] = None,
    scale: float,
    aq_scale: Optional[jnp.ndarray] = None,
    act_bits: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """MLA absorbed-decode latent attention over paged compressed pools.

    ``q_lat (B, H, R)`` is the query already absorbed through the up-proj's
    key half, ``q_pe (B, H, P)`` the rope half; pools ``ckvp (NB, bs, R)`` /
    ``kpep (NB, bs, P)`` hold the shared latent + rope key per token (no head
    axis — that is the MLA bandwidth win), table ``bt (B, MB)``, ``lengths``
    counting valid tokens including this step's write.  Returns the latent
    output ``o_lat (B, H, R)`` (fp32); the caller up-projects through
    ``w_v``.  Oracle: ``ref.ref_paged_mla_attention``.

    ``ckvs``/``kpes`` (``(NB, bs)`` fp32): the pools are integer — int8
    codes, or packed int4 at half feature width when uint8 — and the kernel
    dequantizes in-register.  ``aq_scale``/``act_bits`` replay the absorb
    path's activation fake-quant on the dequantized latent.  ``scale`` is the
    absorbed score scale ``(qk_nope_dim + qk_rope_dim) ** -0.5`` (required —
    not derivable from latent shapes)."""
    if (ckvs is None) != (kpes is None):
        raise ValueError("paged_mla_attention: ckvs and kpes must be given together")
    return paged_mla_attention_pallas(
        q_lat,
        q_pe,
        ckvp,
        kpep,
        bt,
        lengths,
        ckvs,
        kpes,
        scale=scale,
        aq_scale=aq_scale,
        act_bits=act_bits,
        interpret=_default_interpret(interpret),
    )


def rwkv6_scan(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    initial_state: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """RWKV-6 scan over ``(B, H, T, Dk/Dv)`` tensors with per-head bonus
    ``u (H, Dk)``.  Pads T with no-op steps (k=0 so kv=0, w=1 so S unchanged)."""
    B, H, T, Dk = r.shape
    Dv = v.shape[-1]
    ct = min(chunk, _round_up(T, 8))
    Tp = _round_up(T, ct)
    fold = lambda x: x.reshape(B * H, *x.shape[2:])
    rp = _pad_axis(fold(r), 1, Tp)
    kp = _pad_axis(fold(k), 1, Tp)
    vp = _pad_axis(fold(v), 1, Tp)
    wp = _pad_axis(fold(w), 1, Tp, value=1)
    uf = jnp.broadcast_to(u[None], (B, H, Dk)).reshape(B * H, Dk)
    if initial_state is not None:
        s0 = initial_state.reshape(B * H, Dk, Dv)
    else:
        s0 = None
    y, sT = rwkv6_scan_pallas(
        rp, kp, vp, wp, uf, s0, chunk=ct, interpret=_default_interpret(interpret)
    )
    return y[:, :T].reshape(B, H, T, Dv), sT.reshape(B, H, Dk, Dv)
