"""Causal self-attention for training: the installed splash kernel, per KV head.

The kernel is ``jax.experimental.pallas.ops.tpu.splash_attention`` in its
MQA form: one KV head's ``(T, Dh)`` keys and values against the ``G``
query heads that share them, ``(G, T, Dh)``.  ``vmap`` over batch rows and
KV heads turns that into the whole grouped-query layer without copying K/V
per query head.  Its mask is ``CausalMask``, so blocks that lie wholly
above the diagonal are neither fetched nor computed, in the forward pass
and in both backward passes (``dq`` and ``dkv``).  The score matrix lives
in VMEM one block at a time and never reaches HBM.

Numerics: q, k and v enter in their own dtype (bf16 on the training path);
q is scaled by ``1/sqrt(Dh)`` in float32 and rounded back once (exact for
``Dh`` a power of four, e.g. 64).  Scores, the running max and sum, and the
output accumulator are float32; probabilities enter the PV matmul in v's
dtype.  The mask is ``kpos <= qpos`` over positions ``0 .. T-1`` of every
row, which is what a cache-less call of the attention layer passes.

Block sizes follow from the shapes (``block_size``): the largest of 512,
256 and 128 that divides ``T`` into at least four blocks, so that skipping
the blocks above the diagonal saves at least 3/8 of the square; else 128.
A ``T`` that 128 does not divide has no kernel.
The splash kernels' names, which the compiled program keeps, are
``splash_mqa_fwd_no_residuals`` (a forward with no backward after it),
``splash_mqa_fwd_residuals`` (a forward whose log-sum-exp the backward
reads), ``splash_mqa_dq`` and ``splash_mqa_dkv`` (``KERNEL_PASSES``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

__all__ = ["KERNEL_PASSES", "block_size", "causal_attention_pallas"]

_BLOCKS = (512, 256, 128)
_MIN_BLOCKS = 4

# kernel name prefix of each pass -> the pass it belongs to
KERNEL_PASSES = {
    "splash_mqa_fwd": "fwd",
    "splash_mqa_dq": "dq",
    "splash_mqa_dkv": "dkv",
}


def block_size(T: int) -> Optional[int]:
    """The kernel's query and key block for ``T`` positions, or None when
    ``T`` is no multiple of any block (no kernel for that length)."""
    if T % _BLOCKS[-1]:
        return None
    return next((b for b in _BLOCKS if T % b == 0 and T // b >= _MIN_BLOCKS), _BLOCKS[-1])


def _kernel(G: int, T: int, interpret: bool):
    b = block_size(T)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b,
    )
    mask = splash.MultiHeadMask([splash.CausalMask((T, T))] * G)
    return splash.make_splash_mqa_single_device(mask, block_sizes=sizes, interpret=interpret)


def causal_attention_pallas(
    q: jnp.ndarray,  # (B, T, H, Dh)
    k: jnp.ndarray,  # (B, T, KV, Dh)
    v: jnp.ndarray,  # (B, T, KV, Dh)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention of ``q`` over ``k``/``v``, query heads laid out
    ``KV x G`` (head ``h`` reads KV head ``h // G``).  ``T`` must be a
    multiple of ``block_size(T)``.  Returns ``(B, T, H, Dh)`` in q's dtype."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    if block_size(T) is None:
        raise ValueError(f"no causal-attention block divides T={T}")
    qs = (q.astype(jnp.float32) * Dh**-0.5).astype(q.dtype)
    qg = qs.reshape(B, T, KV, G, Dh).transpose(0, 2, 3, 1, 4)  # (B, KV, G, T, Dh)
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, T, Dh)
    vt = v.transpose(0, 2, 1, 3)
    kernel = _kernel(G, T, interpret)
    out = jax.vmap(jax.vmap(kernel))(qg, kt, vt)  # (B, KV, G, T, Dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh)
