"""Pallas TPU kernel: tiled int8 x int8 -> int32 matmul with accumulator
bit-width emulation and an A2Q-enabled int16 partial-sum spill path.

TPU adaptation of the paper's FPGA payoff (DESIGN.md Sec. 2): on FINN
accelerators a small accumulator shrinks the adder/register; on TPU the MXU
datapath is fixed (int8 x int8 -> int32), but the A2Q guarantee that *every*
partial sum fits ``P`` bits unlocks:

* ``spill_dtype=int16`` — when P <= 16, the carried inter-K-tile partial sums
  are provably representable in int16, so the VMEM accumulator scratch (and any
  HBM spill of partial sums in very-large-K matmuls) is half-width.  The cast
  is lossless *because of* the A2Q bound — this is the kernel-level beyond-FPGA
  payoff of the paper's method.
* ``mode='wrap' | 'saturate'`` — exact emulation of a P-bit accumulator, used
  by the overflow benchmarks (Fig. 2) and the bit-exactness tests against the
  numpy simulator.

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost (sequential on TPU); the
accumulator lives in VMEM scratch across K steps.  Per-tile dots use the MXU
via ``jax.lax.dot_general(..., preferred_element_type=int32)``.

Fused epilogue (the W8A8 serve path): with ``scale`` (one fp32 scalar per
output column — the per-channel weight scale ``s8`` with the activation scale
already folded in) and optionally ``bias``, the final K step rescales the
int32 accumulator in VMEM and writes the floating-point output directly:
``out = acc * scale + bias``.  The deployed layer then runs
``act_quant(x) -> int8 @ int8 -> int32 -> scaled fp`` in ONE ``pallas_call``
instead of dequantizing ``q8`` to fp32 and paying a bf16 matmul — the int32
accumulator never round-trips through HBM.

Int8-out chaining extends the epilogue and adds a prologue:

* ``offset`` (``(1, N)`` int32, added to the accumulator at flush) corrects
  signed symmetrization of unsigned activations: unsigned 8-bit codes
  ``q ∈ [0, 255]`` don't fit the int8 MXU operand, so the wrapper (or the
  in-kernel prologue) feeds ``q - 128`` and the flush adds
  ``128 * colsum(w)`` back — exact in int32, and the carried partial sums
  ``|Σ (q-128)·w| <= 128·Σ|w|`` stay inside the A2Q ``P``-bit bound, so the
  int16 spill remains lossless.
* ``requant`` — after the fp rescale (+ bias), the flush replays the *next*
  layer's activation quantizer in-register (optional activation function,
  then ``clip(round(y / out_scale))``) and writes int8 codes directly:
  ``int32 acc -> rescale -> act -> round/clamp -> int8 out``.  The chained
  layer then consumes codes without a standalone act-quant dispatch and
  without materializing the fp32 activation.  Unsigned requant targets emit
  symmetrized codes (``q - 128``).
* ``prologue_quant`` — ``x`` arrives fp32 and the kernel quantizes each tile
  before the dot (``clip(round(x / aq_scale))``, symmetrizing when the
  target is unsigned 8-bit).  Used at chain-break points so even the first
  deployed linear after a norm/residual runs without a standalone act-quant
  dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int_matmul_kernel", "int_matmul_pallas"]


def _wrap_bits_i32(v: jnp.ndarray, bits: int) -> jnp.ndarray:
    if bits >= 32:
        return v
    shift = 32 - bits
    return (v << shift) >> shift


def _saturate_bits_i32(v: jnp.ndarray, bits: int) -> jnp.ndarray:
    if bits >= 32:
        return v
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    return jnp.clip(v, lo, hi)


def _int_range(bits: int, signed: bool) -> tuple[int, int]:
    if signed:
        return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return 0, 2**bits - 1


def _apply_act(y: jnp.ndarray, act_fn: Optional[str], cast_dtype) -> jnp.ndarray:
    """Replay the layer's inter-linear activation bit-exactly.

    ``y`` arrives fp32 (the rescaled accumulator).  The layer code first sees
    the linear's output in ``compute_dtype``, so cast first; each activation
    then reproduces the exact cast sequence of its call site: rwkv6
    channel-mix squares relu in compute dtype (no fp32 round-trip), the
    non-gated MLP runs gelu in fp32 then casts back.
    """
    y = y.astype(cast_dtype)
    if act_fn is None:
        pass
    elif act_fn == "relu2":
        y = jnp.square(jax.nn.relu(y))
    elif act_fn == "gelu":
        y = jax.nn.gelu(y.astype(jnp.float32)).astype(cast_dtype)
    else:
        raise ValueError(f"unknown chained activation {act_fn!r}")
    return y.astype(jnp.float32)


def int_matmul_kernel(
    x_ref,
    w_ref,
    *rest,
    k_steps: int,
    acc_bits: int,
    mode: str,
    fused: bool,
    has_bias: bool,
    has_offset: bool = False,
    requant: bool = False,
    out_bits: int = 8,
    out_signed: bool = True,
    act_fn: Optional[str] = None,
    cast_dtype=jnp.float32,
    prologue_quant: bool = False,
    in_bits: int = 8,
    in_signed: bool = True,
):
    """Kernel body. acc_ref dtype is int32 or int16 (the spill path).

    ``rest`` is ``(scale_ref[, bias_ref][, offset_ref][, out_scale_ref]
    [, aq_scale_ref], o_ref, acc_ref)`` when ``fused`` else
    ``(o_ref, acc_ref)`` — operands precede outputs precede scratch.
    """
    if fused:
        it = iter(rest)
        scale_ref = next(it)
        bias_ref = next(it) if has_bias else None
        offset_ref = next(it) if has_offset else None
        out_scale_ref = next(it) if requant else None
        aq_scale_ref = next(it) if prologue_quant else None
    o_ref, acc_ref = rest[-2:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if prologue_quant:
        # Chain-break entry: x arrives fp32; replay act_quant_int in-register
        # (identical divide/round/clip, so bit-exact vs the standalone
        # dispatch), symmetrizing unsigned 8-bit codes into the int8 operand.
        n, p = _int_range(in_bits, in_signed)
        xq = jnp.clip(jnp.round(x_ref[...] / aq_scale_ref[...]), n, p)
        if not in_signed and in_bits == 8:
            xq = xq - 128.0
        x_tile = xq.astype(jnp.int8)
    else:
        x_tile = x_ref[...]
    tile = jax.lax.dot_general(
        x_tile,
        w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    carried = acc_ref[...].astype(jnp.int32)
    total = carried + tile
    if mode == "wrap":
        total = _wrap_bits_i32(total, acc_bits)
    elif mode == "saturate":
        total = _saturate_bits_i32(total, acc_bits)
    elif mode != "exact":
        raise ValueError(f"unknown mode {mode!r}")
    # Lossless by the A2Q bound when acc_ref is int16 (P <= 16): every carried
    # partial sum is guaranteed to fit the narrow register (symmetrized
    # unsigned codes are bounded by 128 < 2^N - 1, so they only tighten it).
    acc_ref[...] = total.astype(acc_ref.dtype)

    @pl.when(k == k_steps - 1)
    def _flush():
        acc = acc_ref[...].astype(jnp.int32)
        if fused:
            if has_offset:
                acc = acc + offset_ref[...]
            out = acc.astype(jnp.float32) * scale_ref[...]
            if has_bias:
                out = out + bias_ref[...]
            if requant:
                y = _apply_act(out, act_fn, cast_dtype)
                qn, qp = _int_range(out_bits, out_signed)
                q = jnp.clip(jnp.round(y / out_scale_ref[...]), qn, qp)
                if not out_signed and out_bits == 8:
                    q = q - 128.0
                o_ref[...] = q.astype(jnp.int8)
            else:
                o_ref[...] = out.astype(o_ref.dtype)
        else:
            o_ref[...] = acc


def int_matmul_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    out_scale: Optional[jnp.ndarray] = None,
    aq_scale: Optional[jnp.ndarray] = None,
    *,
    acc_bits: int = 32,
    mode: str = "exact",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    spill_dtype: Optional[jnp.dtype] = None,
    out_dtype=jnp.float32,
    out_bits: int = 8,
    out_signed: bool = True,
    act_fn: Optional[str] = None,
    cast_dtype=jnp.float32,
    in_bits: int = 8,
    in_signed: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tiled integer matmul.  Inputs must already be padded to block multiples
    (the public wrapper in ``ops.py`` handles padding/slicing and defaults).

    ``spill_dtype=jnp.int16`` requires ``acc_bits <= 16`` — the A2Q guarantee
    is what makes the narrow carry lossless.

    ``scale``/``bias`` (``(1, N)`` fp32) enable the fused epilogue: the output
    is ``acc * scale (+ bias)`` in ``out_dtype`` instead of raw int32.
    ``bias`` requires ``scale``.

    ``offset`` (``(1, N)`` int32) is added to the accumulator at flush (the
    unsigned-symmetrization correction ``128 * colsum(w)``).  ``out_scale``
    (``(1, N)`` fp32) engages the requantizing epilogue — int8 codes out,
    after the optional ``act_fn`` replay in ``cast_dtype``.  ``aq_scale``
    (``(1, K)`` fp32) engages the quantizing prologue — ``x`` arrives fp32
    and each tile is quantized in-register before the dot.  Requant and
    prologue quant need ``mode='exact'`` (P-bit emulation of the *chained*
    datapath is not modeled).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        f"unpadded shapes M={M} N={N} K={K} for blocks {(block_m, block_n, block_k)}"
    )
    if spill_dtype is None:
        spill_dtype = jnp.int32
    if jnp.dtype(spill_dtype) == jnp.dtype(jnp.int16) and acc_bits > 16:
        raise ValueError("int16 partial-sum spill is only sound when acc_bits <= 16 (A2Q bound)")
    fused = scale is not None
    if bias is not None and not fused:
        raise ValueError("fused bias requires an epilogue scale")
    if (offset is not None or out_scale is not None or aq_scale is not None) and not fused:
        raise ValueError("offset/out_scale/aq_scale require an epilogue scale")
    requant = out_scale is not None
    prologue = aq_scale is not None
    if (requant or prologue) and mode != "exact":
        raise ValueError("requant/prologue quant need mode='exact'")

    k_steps = K // block_k
    grid = (M // block_m, N // block_n, k_steps)
    kernel = functools.partial(
        int_matmul_kernel, k_steps=k_steps, acc_bits=acc_bits, mode=mode,
        fused=fused, has_bias=bias is not None, has_offset=offset is not None,
        requant=requant, out_bits=out_bits, out_signed=out_signed,
        act_fn=act_fn, cast_dtype=cast_dtype,
        prologue_quant=prologue, in_bits=in_bits, in_signed=in_signed,
    )
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
    ]
    operands = [x, w]
    if fused:
        epilogue_spec = pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))
        epilogue = [(scale, jnp.float32)]
        if bias is not None:
            epilogue.append((bias, jnp.float32))
        if offset is not None:
            epilogue.append((offset, jnp.int32))
        if out_scale is not None:
            epilogue.append((out_scale, jnp.float32))
        for arr, dt in epilogue:
            assert arr.shape == (1, N), (arr.shape, N)
            in_specs.append(epilogue_spec)
            operands.append(arr.astype(dt))
        if aq_scale is not None:
            assert aq_scale.shape == (1, K), (aq_scale.shape, K)
            in_specs.append(pl.BlockSpec((1, block_k), lambda i, j, k: (0, k)))
            operands.append(aq_scale.astype(jnp.float32))
    if requant:
        final_dtype = jnp.int8
    elif fused:
        final_dtype = out_dtype
    else:
        final_dtype = jnp.int32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), final_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), spill_dtype)],
        interpret=interpret,
        name="int_matmul",
    )(*operands)
