"""Logical-axis sharding rules with divisibility-aware fallback.

Every parameter in the repo is ``Boxed`` with *logical* axis names
(``nn/module.py``): ``embed``, ``heads``, ``kv_heads``, ``mlp``, ``experts``,
``vocab``, ``layers``, plus the activation-only ``batch``.  This module maps
those names onto mesh axes:

* ``ShardingRules.rules[name]``   — ordered tuple of mesh axes the logical
  axis *wants* to shard over (Megatron-style TP on ``model``, FSDP on
  ``data``, outer DP on ``pod``);
* ``ShardingRules.unit_counts[name]`` — how many *semantic units* the axis
  carries (heads, experts, ffn channels...).  A dim only shards when its unit
  count divides the mesh extent: smollm's 9 heads never split over a 16-way
  ``model`` axis even though the fused ``9 * 64 = 576`` dim would divide —
  splitting mid-head would break per-head attention.  Such dims *replicate*
  instead (the divisibility fallback), which is always correct, just wider.

``resolve_pspec`` additionally never reuses one mesh axis for two dims of the
same array (an invalid ``PartitionSpec``): earlier dims win, later dims fall
back to replication.

``param_specs`` / ``cache_specs`` walk boxed-param / decode-cache pytrees and
return ``PartitionSpec`` trees; ``constrain`` is the mesh-optional
``with_sharding_constraint`` used inside the model forward pass, and
``make_mesh`` builds the meshes it accepts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.nn.module import Boxed

__all__ = [
    "ShardingRules",
    "resolve_pspec",
    "param_specs",
    "cache_specs",
    "constrain",
    "make_mesh",
]


def _prod(vals) -> int:
    out = 1
    for v in vals:
        out *= v
    return out


def _gcd_all(vals: Sequence[int]) -> Optional[int]:
    """gcd of all values (a sharding must divide *every* stack's count)."""
    out = 0
    for v in vals:
        out = math.gcd(out, int(v))
    return out or None


@dataclasses.dataclass
class ShardingRules:
    """Logical-axis -> mesh-axis mapping plus per-axis semantic unit counts."""

    rules: dict
    unit_counts: dict

    @staticmethod
    def default(
        mesh,
        arch,
        *,
        fsdp: bool = True,
        seq_shard_extra: bool = False,
        tp_extra: bool = False,
    ) -> "ShardingRules":
        """Derive the production layout from the mesh axes + arch dims.

        ``data`` carries FSDP (and batch), ``model`` carries TP/EP, ``pod`` is
        the outer data-parallel axis (batch spans ``("pod", "data")`` on a
        multi-pod mesh).  ``arch=None`` yields activation-only rules with no
        unit counts (everything parameter-ish replicates).

        Toggles (dry-run hillclimb levers): ``fsdp=False`` keeps params
        unsharded over ``data``; ``tp_extra`` widens ``vocab`` onto ``data``
        as well; ``seq_shard_extra`` marks the activation ``seq`` axis for
        sharding over ``model``.
        """
        names = tuple(mesh.axis_names)
        model = ("model",) if "model" in names else ()
        data = ("data",) if "data" in names else ()
        batch = tuple(a for a in ("pod", "data") if a in names)
        rules = {
            "batch": batch,
            "embed": data if fsdp else (),
            "heads": model,
            "kv_heads": model,
            "mlp": model,
            "experts": model,
            "vocab": model + (data if tp_extra else ()),
            "layers": (),  # scan-over-layers stacked dim: never sharded
            "seq": model if seq_shard_extra else (),
        }

        unit_counts: dict = {}
        if arch is not None:
            heads: list = []
            kv_heads: list = []
            mlp: list = []
            experts: list = []
            for s in arch.stacks:
                if s.attn is not None:
                    heads.append(s.attn.heads)
                    kv_heads.append(s.attn.kv_heads)
                if s.ssm is not None and arch.d_model % s.ssm.head_dim == 0:
                    heads.append(arch.d_model // s.ssm.head_dim)
                if s.d_ff:
                    mlp.append(s.d_ff)
                if s.moe is not None:
                    mlp.append(s.moe.d_ff)
                    experts.append(s.moe.n_experts)
                    if s.moe.n_shared:
                        mlp.append(s.moe.shared_d_ff or s.moe.d_ff * s.moe.n_shared)
            unit_counts["embed"] = arch.d_model
            unit_counts["vocab"] = arch.vocab
            for name, count in (
                ("heads", _gcd_all(heads)),
                ("kv_heads", _gcd_all(kv_heads)),
                ("mlp", _gcd_all(mlp)),
                ("experts", _gcd_all(experts)),
            ):
                if count is not None:
                    unit_counts[name] = count
        return ShardingRules(rules=rules, unit_counts=unit_counts)


def resolve_pspec(dims, shape, mesh, rules: ShardingRules) -> P:
    """Resolve per-dim logical names to a valid ``PartitionSpec``.

    For each dim: take the rule's mesh axes (skipping axes already used by an
    earlier dim and trivial size-1 axes), then keep the order-preserving
    subset with the *largest* mesh extent such that both the dim's unit count
    and its actual size divide it — so ``batch: ("pod", "data")`` with a
    batch of 8 on a ``{pod: 2, data: 8}`` mesh shards 8-way over ``data``
    rather than 2-way over ``pod``.  Ties prefer earlier axes.  No valid
    subset -> the dim replicates.
    """
    used: set = set()
    entries = []
    for name, dim in zip(dims, shape):
        want = rules.rules.get(name) if name is not None else None
        if not want:
            entries.append(None)
            continue
        candidates = tuple(
            a for a in want
            if a in mesh.shape and mesh.shape[a] > 1 and a not in used
        )
        units = rules.unit_counts.get(name, dim)
        axes, best_extent = (), 1
        for mask in range(1, 1 << len(candidates)):
            subset = tuple(a for i, a in enumerate(candidates) if mask >> i & 1)
            extent = _prod(mesh.shape[a] for a in subset)
            if extent > best_extent and units % extent == 0 and dim % extent == 0:
                axes, best_extent = subset, extent
        if not axes:
            entries.append(None)
            continue
        used.update(axes)
        entries.append(axes[0] if len(axes) == 1 else axes)
    return P(*entries)


def param_specs(boxed_tree, mesh, rules: ShardingRules):
    """Boxed-param tree -> ``PartitionSpec`` tree (unboxed structure).

    Works on real arrays and on ``jax.eval_shape`` trees alike (the dry-run
    never allocates).  Plain (non-boxed) leaves replicate.
    """

    def one(leaf):
        if isinstance(leaf, Boxed):
            return resolve_pspec(leaf.axes, leaf.shape, mesh, rules)
        return P(*([None] * getattr(leaf, "ndim", 0)))

    return jax.tree.map(one, boxed_tree, is_leaf=lambda x: isinstance(x, Boxed))


def cache_specs(cache_tree, mesh, rules: ShardingRules):
    """Decode-cache tree -> ``PartitionSpec`` tree.

    Cache leaves are stacked ``(layers, batch, ...)`` arrays
    (``init_stack_cache``); the batch dim shards over the batch axes when
    divisible (``long_500k``'s batch=1 replicates via the same fallback) and
    the sequence dims stay local so a decode step never gathers its cache.
    Head-carrying leaves additionally shard their head dim over the model
    axis, mirroring the TP layout of the K/V projections that fill them:
    GQA ``k``/``v`` are ``(layers, batch, slots, kv_heads, head_dim)`` and
    take the ``kv_heads`` rule on dim 3; SSM states ``S`` are
    ``(layers, batch, heads, ...)`` and take the ``heads`` rule on dim 2.
    The unit-count fallback applies as everywhere: smollm's 3 kv_heads never
    split over a 16-way model axis — those leaves replicate the head dim.

    Paged layouts (``serve/paged_cache.py``) have no batch dim: block pools
    ``kp``/``vp`` are ``(layers, num_blocks, block_size, kv_heads, head_dim)``
    — any sequence may own any block, so the block axis stays *local*
    (replicated over the batch axes) while the head dim keeps the same TP
    sharding as the projections that fill it.  MLA latent pools
    ``ckvp``/``kpep`` and the block table ``bt (slots, max_blocks)`` carry no
    shardable parameter dim at all (the table rides with the batch).

    int8 pools (``kv_quant``) change dtype, not layout — the same specs
    apply — and add per-slot fp32 scale pools: GQA ``kps``/``vps``
    ``(layers, NB, bs, kv_heads)`` shard their trailing head dim over
    ``model`` exactly like the code pools they scale (a TP shard must hold
    the scales for its own heads); MLA ``ckvs``/``kpes`` ``(layers, NB, bs)``
    carry nothing shardable and replicate.

    Allocator bookkeeping leaves (``PagedKVCache.device_state``): the write
    watermarks ``wm (slots,)`` ride with the batch axes like the block table
    row they describe; the block refcounts ``rc (num_blocks,)`` replicate —
    copy-on-write decisions need the whole allocator state on every shard,
    mirroring the block axis being local in the pools.
    """

    def one(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else None
        if name == "wm":
            # per-slot write watermarks (speculative rollback bookkeeping):
            # one scalar per sequence — rides with the batch like the table
            return resolve_pspec(("batch",) + (None,) * (leaf.ndim - 1), leaf.shape, mesh, rules)
        if name == "rc":
            # per-block refcounts (CoW/prefix-sharing bookkeeping): block
            # axis is local like the pools it counts — every shard must see
            # the whole allocator state, so it replicates
            return P(*([None] * leaf.ndim))
        if leaf.ndim < 2:
            return P(*([None] * leaf.ndim))
        if name == "bt":
            return resolve_pspec(("batch",) + (None,) * (leaf.ndim - 1), leaf.shape, mesh, rules)
        if name in ("kp", "vp", "ckvp", "kpep", "kps", "vps", "ckvs", "kpes"):
            dims = ["layers"] + [None] * (leaf.ndim - 1)
            if name in ("kp", "vp") and leaf.ndim == 5:
                dims[3] = "kv_heads"
            elif name in ("kps", "vps") and leaf.ndim == 4:
                dims[3] = "kv_heads"
            return resolve_pspec(tuple(dims), leaf.shape, mesh, rules)
        dims = ["layers", "batch"] + [None] * (leaf.ndim - 2)
        if name in ("k", "v") and leaf.ndim == 5:
            dims[3] = "kv_heads"
        elif name == "S" and leaf.ndim == 5:
            dims[2] = "heads"
        return resolve_pspec(tuple(dims), leaf.shape, mesh, rules)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def constrain(x, mesh, spec: P):
    """``with_sharding_constraint`` that is a no-op without a mesh (tests /
    single device) — the model forward pass calls this unconditionally."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.  Its default, ``Explicit``,
    refuses the ``with_sharding_constraint`` calls of ``constrain``: the
    model lets GSPMD propagate shardings between those constraints."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )
