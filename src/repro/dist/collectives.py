"""Accumulator-aware compressed collectives.

``compressed_psum`` extends A2Q's per-device guarantee (paper Sec. 3-4:
invert the accumulator bound into a constraint on what gets summed) to the
cross-device reduction.  It is the standard two-phase compressed all-reduce
(1-bit-Adam / EF-SGD lineage), with every quantization error folded into a
single shard-local *error-feedback residual*:

1. each shard adds its residual to the payload (what compression dropped last
   round re-enters this round, so per-step quantization error does not
   accumulate over training) and quantizes to ``bits``-bit integers on a
   *shared* scale (a ``pmax`` across the axis — one scalar per tensor, or one
   fp32 scalar per output column with ``scale_axis="column"``, the A2Q+-style
   per-channel granularity);
2. **phase 1 (scatter)**: the flat int8/int16 payload is split into one chunk
   per shard and exchanged with ``all_to_all`` — each shard becomes the owner
   of one chunk and accumulates the ``n_shards`` quantized contributions
   locally in int32, exactly;
3. **phase 2 (gather)**: the owner requantizes its chunk-sum back to ``bits``
   wide integers on the statically-widened scale ``n_shards * scale`` (safe:
   ``|sum| <= n_shards * qmax``) and ``all_gather``\\ s the low-bit result.
   The requantization error is scattered into the owner's residual at the
   owned positions, so both phases are error-fed-back.

What crosses the wire per call is therefore ~``2 * bits/8`` bytes per element
(one all-to-all + one all-gather of ``bits``-wide integers) versus ~8 bytes
per element for a ring fp32 all-reduce — a ~4x wire-byte reduction at int8,
independent of the axis size.

Overflow avoidance is by construction, mirroring paper Eq. 12: every summand
is bounded by ``qmax = 2**(bits-1) - 1``, so the int32 chunk accumulation over
``n_shards`` devices is exact whenever ``n_shards * qmax <= 2**31 - 1`` —
for int8 that holds up to ~16.9M devices.  The axis size is resolved
*statically* from the trace-time axis environment and the guard raises at
trace time (a traced ``psum(1, axis)`` would silently never fire).

Use inside ``jax.shard_map``; both the payload and the residual are
shard-local (``P(axis, ...)`` in and out).

**Two transports, one wire format.**  ``compressed_psum`` is the
*fully-manual* transport: it spells out the collectives (``all_to_all`` /
``all_gather``) and is the right tool inside a shard_map that is manual over
every mesh axis.  The train step, however, runs the model under GSPMD (TP
over ``model`` etc.), and on the pinned jaxlib XLA's SPMD partitioner
*fatally rejects* gather-family collectives and ``axis_index`` inside a
partially-manual (``auto``-axes) shard_map — scanned attention blocks crash
``hlo_sharding_util`` outright.  ``compressed_allreduce`` is therefore the
*global-view* twin used by ``build_train_step``: same quantization, same
two-phase wire (the all-to-all and all-gather are expressed as
``with_sharding_constraint`` reshards that GSPMD lowers to the identical s8
collectives), same error-feedback algebra — but phase-2 requantization error
lands in an explicit per-owner ``server`` residual instead of being scattered
by ``axis_index``.  Residual state for the global form is the pair
``{"local", "server"}`` (see ``train.state.init_grad_err``).
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "GradCompressConfig",
    "resolve_grad_compress",
    "quantize_shared_scale",
    "compressed_psum",
    "compressed_psum_tree",
    "compressed_allreduce",
    "compressed_allreduce_tree",
    "owner_dim",
    "server_shape",
    "strip_axis",
]

_I32_MAX = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class GradCompressConfig:
    """Wire format for the data-parallel gradient reduction.

    ``bits``        integer width of the wire payload (2..16).
    ``scale_axis``  "tensor": one shared fp32 scale per gradient leaf;
                    "column": one fp32 scale per output column (last dim) of
                    rank>=2 leaves — A2Q+-style per-channel granularity;
                    rank<2 leaves fall back to the tensor scale.
    ``axis``        mesh axis to reduce over; ``None`` resolves to ``"pod"``
                    when the mesh has one (the DCN-crossing reduction — the
                    expensive wire), else ``"data"``.
    """

    bits: int = 8
    scale_axis: Literal["tensor", "column"] = "tensor"
    axis: Optional[str] = None


def resolve_grad_compress(cfg: Optional[GradCompressConfig], mesh) -> Optional[GradCompressConfig]:
    """Pin ``cfg.axis`` to a concrete mesh axis, or return ``None`` when
    compression cannot apply (no mesh / axis absent / axis extent 1)."""
    if cfg is None or mesh is None:
        return None
    axis = cfg.axis or ("pod" if "pod" in mesh.shape else "data")
    if axis not in mesh.shape or mesh.shape[axis] <= 1:
        return None
    return dataclasses.replace(cfg, axis=axis)


def _static_axis_size(axis) -> Optional[int]:
    """Size of a mesh axis (or tuple of axes) at trace time, or ``None``
    when the axis is not bound (outside ``shard_map``)."""
    try:
        return int(jax.lax.axis_size(axis))
    except NameError:
        return None


def quantize_shared_scale(y: jnp.ndarray, axis, bits: int, scale_axis: str = "tensor"):
    """Symmetric integer quantization on a scale agreed across ``axis``.

    Returns ``(q, scale)`` — the wire payload (int8 for ``bits <= 8``, else
    int16) and the fp32 scale, broadcastable against ``y``: shape ``()`` for
    ``scale_axis="tensor"``, ``(1, ..., 1, C)`` (one scale per output column)
    for ``scale_axis="column"`` on rank>=2 payloads.
    """
    qmax = 2 ** (bits - 1) - 1
    wire_dtype = jnp.int8 if bits <= 8 else jnp.int16
    if scale_axis == "column" and y.ndim >= 2:
        absmax = jnp.max(jnp.abs(y), axis=tuple(range(y.ndim - 1)), keepdims=True)
    else:
        absmax = jnp.max(jnp.abs(y))
    gmax = jax.lax.pmax(absmax, axis)
    scale = jnp.maximum(gmax, jnp.finfo(jnp.float32).tiny) / qmax
    q = jnp.clip(jnp.round(y / scale), -qmax, qmax).astype(wire_dtype)
    return q, scale


def compressed_psum(
    x: jnp.ndarray,
    axis,
    err: jnp.ndarray,
    bits: int = 8,
    scale_axis: str = "tensor",
):
    """int-quantized all-reduce over mesh axis ``axis`` with error feedback.

    Args:
        x:    shard-local payload (e.g. this shard's gradient contribution).
        axis: mesh axis name to reduce over.
        err:  shard-local residual carried from the previous call
              (``jnp.zeros_like(x)`` on the first).
        bits: integer width of the wire format (2..16).
        scale_axis: "tensor" (one shared scale) or "column" (one fp32 scale
              per last-dim column of rank>=2 payloads; rank<2 payloads use
              the tensor scale).

    Returns ``(total, new_err)``: the (dequantized) sum, replicated along
    ``axis``, and the residual to feed back next call.
    """
    if not 2 <= bits <= 16:
        raise ValueError(f"bits must be in [2, 16], got {bits}")
    if scale_axis not in ("tensor", "column"):
        raise ValueError(f"scale_axis must be 'tensor' or 'column', got {scale_axis!r}")
    n_shards = _static_axis_size(axis)
    if n_shards is None:
        raise ValueError(
            f"compressed_psum: axis {axis!r} is not bound to a static size — "
            "call it inside jax.shard_map over that mesh axis"
        )
    qmax = 2 ** (bits - 1) - 1
    if n_shards * qmax > _I32_MAX:
        raise ValueError(
            f"int32 accumulator can overflow: {n_shards} shards * qmax {qmax} "
            f"= {n_shards * qmax} > {_I32_MAX}"
        )

    y = (x + err).astype(jnp.float32)
    q, scale = quantize_shared_scale(y, axis, bits, scale_axis)
    err1 = y - q.astype(jnp.float32) * scale  # phase-1 EF: what quantization dropped

    # flat chunk layout: shard i owns elements [i*chunk, (i+1)*chunk)
    nelem = q.size
    chunk = -(-nelem // n_shards)
    pad = chunk * n_shards - nelem
    scale_flat = jnp.pad(
        jnp.broadcast_to(scale, y.shape).reshape(-1), (0, pad), constant_values=1.0
    )
    idx = jax.lax.axis_index(axis)
    my_scale = jax.lax.dynamic_slice(scale_flat, (idx * chunk,), (chunk,))

    # phase 1: all_to_all the low-bit chunks; owner accumulates in int32
    # (exact by the static guard above)
    sent = jnp.pad(q.reshape(-1), (0, pad)).reshape(n_shards, chunk)
    recv = jax.lax.all_to_all(sent, axis, split_axis=0, concat_axis=0)
    chunk_sum = jnp.sum(recv.astype(jnp.int32), axis=0)

    # phase 2: requantize the chunk-sum onto the statically-widened scale
    # (|sum| <= n_shards * qmax, so sum / n_shards fits back in qmax) and
    # all-gather the low-bit result; the requantization error is the owner's
    # to feed back
    value_sum = chunk_sum.astype(jnp.float32) * my_scale
    wide = my_scale * n_shards
    q2 = jnp.clip(jnp.round(chunk_sum.astype(jnp.float32) / n_shards), -qmax, qmax)
    q2 = q2.astype(q.dtype)
    err2_chunk = value_sum - q2.astype(jnp.float32) * wide
    gathered = jax.lax.all_gather(q2, axis, tiled=True)
    total = gathered.astype(jnp.float32)[:nelem] * scale_flat[:nelem] * n_shards
    total = total.reshape(x.shape)

    # phase-2 EF: scatter the owner's requantization error into its owned
    # positions of the (param-shaped) residual
    err2_flat = jax.lax.dynamic_update_slice(
        jnp.zeros((chunk * n_shards,), jnp.float32), err2_chunk, (idx * chunk,)
    )
    new_err = err1 + err2_flat[:nelem].reshape(x.shape)
    return total.astype(x.dtype), new_err.astype(err.dtype)


def compressed_psum_tree(tree, axis, err_tree, bits: int = 8, scale_axis: str = "tensor"):
    """``compressed_psum`` over a pytree (e.g. a gradient tree).

    Returns ``(total_tree, new_err_tree)`` with the input structures.
    """
    flat, treedef = jax.tree_util.tree_flatten(tree)
    err_flat = treedef.flatten_up_to(err_tree)
    totals, errs = [], []
    for leaf, err in zip(flat, err_flat):
        t, e = compressed_psum(leaf, axis, err, bits, scale_axis)
        totals.append(t)
        errs.append(e)
    return (
        jax.tree_util.tree_unflatten(treedef, totals),
        jax.tree_util.tree_unflatten(treedef, errs),
    )


# ---------------------------------------------------------------------------
# Global-view transport (GSPMD / jit world) — see module docstring for why
# the train step cannot use the shard_map transport on this jaxlib.
# ---------------------------------------------------------------------------


def owner_dim(pspec, ndim: int, axis: str) -> int:
    """Payload dim that carries the ownership split after the all-to-all.

    Prefer the dim the param layout already shards over ``axis`` (the FSDP
    dim): ownership then coincides with the param's own slice, the phase-2
    result *is* the param layout and costs zero wire (ZeRO-style: each
    device ends up with exactly its gradient slice).  Otherwise the first
    dim that claims no other mesh axis — a TP-sharded dim (e.g. ``vocab``
    over ``model`` on the embedding table) keeps its sharding on the wire
    and only ``1/tp``-th of the payload crosses each link.

    A dim counts as the FSDP dim whether the spec spells it bare
    (``P("data", ...)``) or inside a multi-axis tuple (``P(("pod", "data"),
    ...)`` — the multi-pod batch layout): missing the tuple form used to
    push ownership onto a free dim and cost an extra all-gather on the wire
    for every FSDP leaf of a multi-pod mesh."""
    entries = (list(pspec or ()) + [None] * ndim)[:ndim]
    for i, e in enumerate(entries):
        if e == axis or (isinstance(e, tuple) and axis in e):
            return i
    for i, e in enumerate(entries):
        if e is None:
            return i
    return 0


def server_shape(shape, n_shards: int, owner: int = 0) -> tuple:
    """Shape of the phase-2 (server) residual for a payload of ``shape``:
    the payload with dim ``owner`` padded up to a multiple of ``n_shards``
    (that dim carries the ownership split after the all-to-all); scalars
    stack to ``(n_shards,)``."""
    eff = tuple(int(d) for d in shape) or (1,)
    padded = -(-eff[owner] // n_shards) * n_shards
    return eff[:owner] + (padded,) + eff[owner + 1:]


def strip_axis(entries, axis):
    """Remove ``axis`` from a list of PartitionSpec entries (replaced by
    ``None`` / dropped from tuples) — a spec may not mention one mesh axis
    twice, and the residual/wire layouts reserve ``axis`` for the shard or
    owner dim."""
    out = []
    for e in entries:
        if e == axis:
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != axis)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            out.append(e)
    return out


def _constrain(x, mesh, spec):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def compressed_allreduce(
    g: jnp.ndarray,
    err_local: jnp.ndarray,
    err_server: jnp.ndarray,
    *,
    mesh,
    axis: str,
    bits: int = 8,
    scale_axis: str = "tensor",
    pspec=None,
):
    """Global-view compressed sum over the leading (per-shard) dim of ``g``.

    Args:
        g:          ``(n_shards, *shape)`` stacked per-shard contributions,
                    sharded ``P(axis, ...)`` (each device row holds its own
                    shard; payload dims may carry any other-axis sharding).
        err_local:  fp32 ``(n_shards, *shape)`` phase-1 residual (same layout).
        err_server: fp32 ``server_shape(shape, n_shards, owner)`` phase-2
                    (requantization) residual, owner-dim-sharded over ``axis``.
        mesh/axis:  mesh and axis the shard dim is laid out on.
        bits/scale_axis: wire format, as in ``compressed_psum``.
        pspec:      the payload's param ``PartitionSpec`` (its layout in the
                    optimizer state).  Picks the ownership dim
                    (``owner_dim``) and keeps every *other* mesh axis's
                    sharding intact through the wire, so TP-sharded leaves
                    move only their local slice.  ``None`` = unsharded layout
                    (ownership on dim 0).

    Returns ``(total, new_err_local, new_err_server)``; ``total`` has shape
    ``shape``, replicated over ``axis`` (other axes keep the param layout).
    The s8/s16 wire traffic is emitted by GSPMD from the sharding-constraint
    reshards: the all-to-all moves the ``axis`` shard from the stack dim to
    the payload's owner dim, the all-gather removes it again after the int32
    accumulation.
    """
    if not 2 <= bits <= 16:
        raise ValueError(f"bits must be in [2, 16], got {bits}")
    if scale_axis not in ("tensor", "column"):
        raise ValueError(f"scale_axis must be 'tensor' or 'column', got {scale_axis!r}")
    n = int(mesh.shape[axis])
    if g.shape[0] != n:
        raise ValueError(f"leading dim {g.shape[0]} != axis {axis!r} extent {n}")
    qmax = 2 ** (bits - 1) - 1
    if n * qmax > _I32_MAX:
        raise ValueError(
            f"int32 accumulator can overflow: {n} shards * qmax {qmax} > {_I32_MAX}"
        )
    wire_dtype = jnp.int8 if bits <= 8 else jnp.int16
    shape = g.shape[1:]
    scalar = shape == ()
    if scalar:
        g = g[:, None]
        err_local = err_local[:, None]
        shape = (1,)
    ndim = len(shape)
    od = owner_dim(pspec, ndim, axis)
    entries_orig = (list(pspec or ()) + [None] * ndim)[:ndim]
    entries = strip_axis(entries_orig, axis)

    y = g.astype(jnp.float32) + err_local
    # scale shared across shards: the max over the (sharded) leading dim is
    # the global-view pmax — a tiny fp32 all-reduce
    if scale_axis == "column" and y.ndim >= 3:
        absmax = jnp.max(jnp.abs(y), axis=tuple(range(y.ndim - 1)), keepdims=True)
    else:
        absmax = jnp.max(jnp.abs(y))
    scale = jnp.maximum(absmax, jnp.finfo(jnp.float32).tiny) / qmax
    q = jnp.clip(jnp.round(y / scale), -qmax, qmax).astype(wire_dtype)
    new_local = y - q.astype(jnp.float32) * scale

    d_own = shape[od]
    d_pad = -(-d_own // n) * n
    if d_pad != d_own:  # pad rows quantize to 0 and stay 0 in the server residual
        pads = [(0, 0)] * q.ndim
        pads[1 + od] = (0, d_pad - d_own)
        q = jnp.pad(q, pads)

    scale1 = scale[0] if scale.ndim else scale  # drop the stack dim
    if d_pad != d_own and scale1.ndim and od == ndim - 1 and scale1.shape[-1] > 1:
        # per-column scales ride along when the owner dim IS the column dim
        scale1 = jnp.pad(scale1, [(0, 0)] * (scale1.ndim - 1) + [(0, d_pad - d_own)],
                         constant_values=1.0)

    # phase 1: move the `axis` shard from the stack dim to the payload's
    # owner dim — an s8/s16 all-to-all
    own = lambda e: entries[:od] + [e] + entries[od + 1:]
    q = _constrain(q, mesh, [axis] + own(None))
    moved = _constrain(q, mesh, [None] + own(axis))
    part_sum = jnp.sum(moved.astype(jnp.int32), axis=0)  # owner-local

    # phase 2: requantize onto the statically-widened scale and un-shard the
    # owner dim — an s8/s16 all-gather; the requantization error stays with
    # the owner as the server residual
    value_sum = part_sum.astype(jnp.float32) * scale1 + err_server
    wide = scale1 * n
    q2 = jnp.clip(jnp.round(value_sum / wide), -qmax, qmax).astype(wire_dtype)
    q2 = _constrain(q2, mesh, own(axis))
    new_server = value_sum - q2.astype(jnp.float32) * wide
    # land the total in the *param* layout: when the owner dim is the
    # param's own `axis` (FSDP) dim this is a no-op — each device already
    # holds exactly its slice of the summed gradient (ZeRO) — otherwise an
    # s8/s16 all-gather over `axis` on the owner dim
    gathered = _constrain(q2, mesh, entries_orig)
    total = gathered.astype(jnp.float32) * wide
    if d_pad != d_own:
        total = jax.lax.slice_in_dim(total, 0, d_own, axis=od)
    if scalar:
        total = total[:, 0].reshape(()) if total.ndim == 2 else total.reshape(())

    return (
        total.astype(g.dtype).reshape(() if scalar else shape),
        new_local[:, 0].astype(err_local.dtype) if scalar else new_local.astype(err_local.dtype),
        new_server.astype(err_server.dtype),
    )


def compressed_allreduce_tree(
    tree, err_tree, *, mesh, axis: str, bits: int = 8, scale_axis: str = "tensor",
    pspec_tree=None,
):
    """``compressed_allreduce`` over a stacked-gradient pytree.

    ``tree`` leaves are ``(n_shards, *shape)``; ``err_tree`` is the residual
    pair ``{"local": like tree, "server": server_shape per leaf}`` produced
    by ``train.state.init_grad_err``; ``pspec_tree`` optionally carries the
    per-leaf param PartitionSpecs (same structure) so TP-sharded leaves keep
    their layout on the wire.  Returns ``(total_tree, new_err_tree)``.
    """
    flat, treedef = jax.tree_util.tree_flatten(tree)
    local_flat = treedef.flatten_up_to(err_tree["local"])
    server_flat = treedef.flatten_up_to(err_tree["server"])
    pspec_flat = (
        treedef.flatten_up_to(pspec_tree) if pspec_tree is not None else [None] * len(flat)
    )
    totals, locals_, servers = [], [], []
    for g, el, es, ps in zip(flat, local_flat, server_flat, pspec_flat):
        t, nl, ns = compressed_allreduce(
            g, el, es, mesh=mesh, axis=axis, bits=bits, scale_axis=scale_axis, pspec=ps
        )
        totals.append(t)
        locals_.append(nl)
        servers.append(ns)
    unflatten = jax.tree_util.tree_unflatten
    return (
        unflatten(treedef, totals),
        {
            "local": unflatten(treedef, locals_),
            "server": unflatten(treedef, servers),
        },
    )
