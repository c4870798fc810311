"""Pallas TPU kernel: blocked online-softmax attention (FlashAttention-2 style).

Grid ``(B*H, Tq/bq, Tk/bk)`` with the KV axis innermost (sequential).  Running
row-max / row-sum / output accumulator live in VMEM scratch; the ``(Tq, Tk)``
score matrix is never materialized, so 32k-token prefill fits VMEM with
``O(bq * bk)`` working set.  Supports:

* causal masking (block-level position arithmetic),
* sliding-window masking (h2o-danube / hymba SWA, llama4 chunked-local is
  lowered to windows by the layer above),
* decode alignment (Tq < Tk with query positions aligned to the sequence end).

Numerics: fp32 softmax state regardless of input dtype, matching the oracle
``ref.ref_flash_attention`` to ~1e-5.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

_NEG_INF = -1e30


def flash_attention_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    kv_steps: int,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)

    # absolute positions; queries are end-aligned for decode (Tq < Tk)
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    qpos = qpos + (seq_k - seq_q)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k  # KV padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # (bq, bk)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = alpha * acc_ref[...] + pv

    @pl.when(ki == kv_steps - 1)
    def _flush():
        l = l_ref[...]
        norm = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = (acc_ref[...] * norm).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    true_q: Optional[int] = None,
    true_k: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q ``(BH, Tq, D)``, k/v ``(BH, Tk, D)`` — heads pre-folded, Tq/Tk padded
    to block multiples (``ops.py`` handles folding/padding).  ``true_q`` /
    ``true_k`` are the unpadded lengths: padded KV columns are masked out and
    query positions are end-aligned against ``true_k`` (padded query rows
    produce garbage that the wrapper slices off)."""
    BH, Tq, D = q.shape
    _, Tk, _ = k.shape
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, Tk, block_q, block_k)
    if scale is None:
        scale = D**-0.5
    true_q = Tq if true_q is None else true_q
    true_k = Tk if true_k is None else true_k

    kv_steps = Tk // block_k
    grid = (BH, Tq // block_q, kv_steps)
    kernel = functools.partial(
        flash_attention_kernel,
        scale=scale,
        causal=causal,
        window=window,
        kv_steps=kv_steps,
        block_q=block_q,
        block_k=block_k,
        seq_q=true_q,
        seq_k=true_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
