"""Pallas TPU kernel: chunked RWKV-6 (Finch) linear-attention scan.

The recurrence (per head, data-dependent per-channel decay ``w_t``)::

    y_t = r_t @ (S + (u * k_t) v_t^T)
    S   = diag(w_t) S + k_t v_t^T

is O(1)-state, which is what makes rwkv6-7b / hymba runnable at 500k context.
The kernel processes the sequence in chunks: grid ``(B*H, T/chunk)`` with the
chunk axis innermost/sequential, the ``(Dk, Dv)`` state carried in fp32 VMEM
scratch across chunks, and an in-chunk ``fori_loop`` over timesteps.  Inputs
stream HBM->VMEM one chunk at a time, so the working set is
``O(chunk * (2 Dk + 2 Dv) + Dk * Dv)`` regardless of T.

The in-chunk loop is step-sequential (the paper-faithful recurrence); the
intra-chunk matmul re-formulation (cumulative decay products + two GEMMs per
chunk, Finch Appendix D) is the MXU-friendly upgrade path and is noted in
EXPERIMENTS.md SPerf.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_scan_kernel", "rwkv6_scan_pallas"]


def rwkv6_scan_kernel(
    r_ref,
    k_ref,
    v_ref,
    w_ref,
    u_ref,
    s0_ref,
    y_ref,
    sT_ref,
    state_ref,
    *,
    chunk: int,
    t_steps: int,
):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load_state():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)  # (Dk,)

    def step(i, _):
        r_t = r_ref[0, i, :].astype(jnp.float32)  # (Dk,)
        k_t = k_ref[0, i, :].astype(jnp.float32)
        v_t = v_ref[0, i, :].astype(jnp.float32)  # (Dv,)
        w_t = w_ref[0, i, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]  # (Dk, Dv)
        S = state_ref[...]
        y = r_t @ (S + u[:, None] * kv)  # (Dv,)
        y_ref[0, i, :] = y.astype(y_ref.dtype)
        state_ref[...] = w_t[:, None] * S + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ti == t_steps - 1)
    def _flush_state():
        sT_ref[0] = state_ref[...]


def rwkv6_scan_pallas(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,
    u: jnp.ndarray,
    initial_state: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """r/k/w ``(BH, T, Dk)``, v ``(BH, T, Dv)``, u ``(BH, Dk)`` (head bonus
    broadcast per batch in the wrapper), state ``(BH, Dk, Dv)``.  T must be a
    chunk multiple (wrapper pads with w=1, k=0 no-op steps).

    Returns (y ``(BH, T, Dv)`` in r.dtype, final state fp32)."""
    BH, T, Dk = r.shape
    Dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    if initial_state is None:
        initial_state = jnp.zeros((BH, Dk, Dv), jnp.float32)

    t_steps = T // chunk
    grid = (BH, t_steps)
    kernel = functools.partial(rwkv6_scan_kernel, chunk=chunk, t_steps=t_steps)
    y, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, Dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, Dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, Dk), lambda b, t: (b, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, Dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, Dk, Dv), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, Dv), r.dtype),
            jax.ShapeDtypeStruct((BH, Dk, Dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(r, k, v, w, u, initial_state)
    return y, sT
