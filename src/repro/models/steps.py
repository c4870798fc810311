"""Step builders shared by the trainer, the serve engine, and the dry-run.

Each builder returns a pure function suitable for ``jax.jit`` with explicit
in/out shardings:

* ``build_train_step``  — fwd + bwd + grad-clip + optimizer update (+donation)
* ``build_prefill_step``— forward over a full prompt, returns last-position
  logits + the populated KV cache
* ``build_serve_step``  — one decode token against a KV cache

The dry-run lowers these exact functions for every (arch x shape x mesh) cell;
nothing is special-cased for compilation.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.dist.collectives import compressed_allreduce_tree, resolve_grad_compress
from repro.dist.sharding import ShardingRules, constrain, param_specs
from repro.models.lm import Runtime, apply_lm, init_cache, init_lm, lm_loss
from repro.optim.optimizers import Optimizer, clip_by_global_norm

__all__ = ["build_train_step", "build_prefill_step", "build_serve_step"]


def _strip_axis_rules(rules: Optional[ShardingRules], axis: str) -> Optional[ShardingRules]:
    """Rules for the per-shard (vmapped) model pass of the compressed step:
    the compression axis carries the *group* dim, so activation constraints
    inside the model may only mention the remaining mesh axes."""
    if rules is None:
        return None
    return ShardingRules(
        rules={k: tuple(a for a in v if a != axis) for k, v in rules.rules.items()},
        unit_counts=dict(rules.unit_counts),
    )


def build_train_step(
    arch: ArchConfig,
    optimizer: Optimizer,
    rt: Optional[Runtime] = None,
    lr_schedule: Optional[Callable] = None,
    grad_clip: float = 1.0,
):
    rt = rt or Runtime()
    lr_schedule = lr_schedule or (lambda step: jnp.asarray(3e-4, jnp.float32))
    gc = resolve_grad_compress(rt.grad_compress, rt.mesh)
    if gc is not None:
        return _build_compressed_train_step(arch, optimizer, rt, lr_schedule, grad_clip, gc)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params, opt_state, step = state["params"], state["opt_state"], state["step"]

        def loss_fn(p):
            return lm_loss(p, arch, batch, rt=rt)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            lr = lr_schedule(step)
            new_params, new_opt = optimizer.update(grads, opt_state, params, lr)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return {"params": new_params, "opt_state": new_opt, "step": step + 1}, metrics

    return train_step


def _build_compressed_train_step(arch, optimizer, rt, lr_schedule, grad_clip, gc):
    """Train step whose data-parallel gradient reduction is the int-quantized
    two-phase ``compressed_allreduce_tree`` instead of the fp32 all-reduce
    GSPMD would emit.

    The global batch is split into ``n_shards`` groups along the compression
    axis (``pod`` on a multi-pod mesh: the DCN-crossing reduction) and the
    fwd+bwd is ``vmap``-ed over groups, so the per-group gradients — the
    quantities the baseline would immediately all-reduce in fp32 — stay
    visible as a stacked ``(n_shards, *shape)`` tree sharded over the axis.
    They then meet on the wire as ``bits``-wide integers via the GSPMD
    reshards inside ``compressed_allreduce_tree``.  (A shard_map over the
    axis would be the more direct spelling, but the pinned jaxlib's SPMD
    partitioner fatally rejects gather-family collectives and scanned
    attention blocks inside a partially-manual shard_map — see
    ``dist/collectives.py``.)

    The error-feedback residual pair is carried in ``state["grad_err"]``
    (see ``train.state.init_grad_err``); the global batch must be a
    multiple of the axis extent.  Grad-clip and the optimizer update run on
    the reduced gradient, exactly as in the uncompressed path.
    """
    mesh, axis = rt.mesh, gc.axis
    n_shards = int(mesh.shape[axis])
    inner_rt = Runtime(
        mesh=mesh,
        ep_axis=rt.ep_axis,
        rules=_strip_axis_rules(rt.rules, axis),
        mla_absorb=rt.mla_absorb,
    )
    # param layout tree: lets the reduction keep TP shardings on the wire
    pspec_tree = None
    if rt.rules is not None:
        boxed_shapes = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch))
        pspec_tree = param_specs(boxed_shapes, mesh, rt.rules)

    def group(t):
        if t.shape[0] % n_shards:
            raise ValueError(
                f"grad_compress: global batch {t.shape[0]} must be a "
                f"multiple of the {axis!r} axis extent {n_shards}"
            )
        t = t.reshape(n_shards, t.shape[0] // n_shards, *t.shape[1:])
        return constrain(t, mesh, P(axis, *([None] * (t.ndim - 1))))

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params, opt_state, step = state["params"], state["opt_state"], state["step"]
        grouped = jax.tree.map(group, batch)

        def loss_fn(p, b):
            return lm_loss(p, arch, b, rt=inner_rt)

        # spmd_axis_name pins the group dim to the compression axis through
        # every op of the vmapped fwd+bwd, so activations keep their
        # group-sharding instead of being gathered at each internal
        # sharding constraint
        (_, metrics), grads = jax.vmap(
            jax.value_and_grad(loss_fn, has_aux=True),
            in_axes=(None, 0),
            spmd_axis_name=axis,
        )(params, grouped)
        # each group saw 1/n of the global batch: the global-mean-loss
        # gradient is the mean of the per-group gradients
        grads = jax.tree.map(lambda g: g / n_shards, grads)
        grads, new_err = compressed_allreduce_tree(
            grads, state["grad_err"], mesh=mesh, axis=axis,
            bits=gc.bits, scale_axis=gc.scale_axis, pspec_tree=pspec_tree,
        )
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            lr = lr_schedule(step)
            new_params, new_opt = optimizer.update(grads, opt_state, params, lr)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return {
            "params": new_params,
            "opt_state": new_opt,
            "step": step + 1,
            "grad_err": new_err,
        }, metrics

    return train_step


def build_prefill_step(arch: ArchConfig, rt: Optional[Runtime] = None, max_seq: Optional[int] = None):
    """Prompt -> (last-position logits, cache filled up to the prompt length).

    The cache is produced by replaying the prompt's K/V into the cache layout
    in one shot (a scatter of the computed K/V), so prefill is a single
    forward pass — not T decode steps.
    """
    rt = rt or Runtime()

    def prefill_step(params: dict, batch: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
        logits, _, _ = apply_lm(
            params, arch,
            tokens=batch.get("tokens"),
            frontend_embeds=batch.get("frontend_embeds"),
            rt=rt,
        )
        return logits[:, -1:, :]

    return prefill_step


def build_serve_step(arch: ArchConfig, rt: Optional[Runtime] = None):
    """(params, tokens (B,1), cache, pos) -> (logits (B,1,V), new cache)."""
    rt = rt or Runtime()

    def serve_step(params: dict, tokens: jnp.ndarray, cache: dict, pos: jnp.ndarray):
        logits, new_cache, _ = apply_lm(
            params, arch, tokens=tokens, cache=cache, start_pos=pos, rt=rt
        )
        return logits, new_cache

    return serve_step
