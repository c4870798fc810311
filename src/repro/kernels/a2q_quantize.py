"""Pallas TPU kernel: fused A2Q weight quantizer (Eq. 20-23 in one pass).

Fuses the whole A2Q inference-side pipeline for a ``(K, C)`` weight matrix —
per-channel l1 norm -> norm cap ``g = 2**min(t, T)`` -> scale -> round-to-zero
-> clip -> dequantize — without materializing any intermediate in HBM.

Two-phase sequential grid ``(C/bc, 2, K/bk)``:

* phase 0 streams the column block over K accumulating ``sum |v|`` into a VMEM
  scratch row (the l1 norm needs all of K before any output element is final);
* phase 1 re-streams the same blocks and emits both the integer weights (int8)
  and the dequantized float weights.

v is read twice from HBM (unavoidable for an exact norm), but the quantize
arithmetic, both outputs, and the norm never round-trip through HBM — versus
four materializations for the unfused jnp path.  Channel blocks are VMEM-sized
so K can be arbitrarily large (command-r's d_ff=22528 columns stream fine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["a2q_quantize_kernel", "a2q_quantize_pallas"]


def a2q_quantize_kernel(
    v_ref,
    t_ref,
    d_ref,
    deq_ref,
    q_ref,
    l1_ref,
    *,
    weight_bits: int,
    acc_bits: int,
    input_bits: int,
    input_signed: bool,
):
    phase = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((phase == 0) & (k == 0))
    def _init():
        l1_ref[...] = jnp.zeros_like(l1_ref)

    @pl.when(phase == 0)
    def _accumulate():
        l1_ref[...] += jnp.sum(
            jnp.abs(v_ref[...].astype(jnp.float32)), axis=0, keepdims=True
        )

    @pl.when(phase == 1)
    def _quantize():
        n = float(-(2 ** (weight_bits - 1)))
        p = float(2 ** (weight_bits - 1) - 1)
        t = t_ref[...]  # (1, bc)
        d = d_ref[...]
        log2_amax = jnp.log2(jnp.float32(2.0 ** (acc_bits - 1) - 1.0))
        T = int(input_signed) + log2_amax + d - input_bits  # Eq. 23
        g_over_s = jnp.exp2(jnp.minimum(t, T) - d)  # g/s, exact in log space
        l1 = jnp.maximum(l1_ref[...], 1e-12)
        v = v_ref[...].astype(jnp.float32)
        q = jnp.clip(jnp.trunc(g_over_s * v / l1), n, p)
        q_ref[...] = q.astype(jnp.int8)
        deq_ref[...] = q * jnp.exp2(d)


def a2q_quantize_pallas(
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
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused quantize of a padded ``(K, C)`` matrix with per-channel ``t``/``d``
    given as ``(1, C)``.  Returns (dequantized float32, integer int8)."""
    K, C = v.shape
    assert t.shape == (1, C) and d.shape == (1, C), (t.shape, d.shape, C)
    assert K % block_k == 0 and C % block_c == 0, (K, C, block_k, block_c)

    grid = (C // block_c, 2, K // block_k)
    kernel = functools.partial(
        a2q_quantize_kernel,
        weight_bits=weight_bits,
        acc_bits=acc_bits,
        input_bits=input_bits,
        input_signed=input_signed,
    )
    deq, q = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, block_c), lambda c, phase, k: (k, c)),
            pl.BlockSpec((1, block_c), lambda c, phase, k: (0, c)),
            pl.BlockSpec((1, block_c), lambda c, phase, k: (0, c)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, block_c), lambda c, phase, k: (k, c)),
            pl.BlockSpec((block_k, block_c), lambda c, phase, k: (k, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, C), jnp.float32),
            jax.ShapeDtypeStruct((K, C), jnp.int8),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
        name="a2q_quantize",
    )(v, t, d)
    return deq, q
