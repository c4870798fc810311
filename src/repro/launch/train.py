"""Training launcher CLI.

Runs a real training loop on whatever devices exist: on this CPU container it
drives reduced configs end-to-end (examples + integration tests); on a TPU
fleet the same entrypoint builds the production mesh and shards state/batches
with the exact same code paths the dry-run compiles.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1

Elastic restart: rerun the same command after changing the device fleet; the
mesh planner re-plans and the checkpoint re-shards onto the new mesh.

Before the first step the launcher compiles the step for the placed state
and logs the census of the cross-chip collectives one step runs
(``dist.census``), recording it as the counters ``train_collectives`` and
``train_collective_bytes`` (label ``kind``) of the trainer's
``obs.metrics``; the trainer's jitted step then runs that compile and asks
for no other (``tests/test_sharding.py`` counts the compiles).
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.data.synthetic import TokenStream
from repro.dist.census import collective_census
from repro.dist.collectives import GradCompressConfig, resolve_grad_compress
from repro.dist.sharding import ShardingRules, make_mesh, param_specs
from repro.launch.cache import enable_compile_cache
from repro.models.lm import Runtime, init_lm
from repro.models.steps import build_train_step
from repro.nn.module import unbox
from repro.obs import Obs
from repro.optim.optimizers import adamw, adafactor, sgdm
from repro.optim.schedules import cosine_with_warmup
from repro.train.checkpoint import install_signal_handler
from repro.train.elastic import StragglerWatchdog, plan_mesh
from repro.train.state import init_grad_err, make_state_specs, specs_to_shardings
from repro.train.trainer import Trainer

_OPTS = {"adamw": adamw, "adafactor": adafactor, "sgdm": sgdm}


def train(
    arch,
    *,
    steps: int,
    batch: int,
    seq: int,
    lr: float = 1e-3,
    optimizer: str = "adamw",
    devices=None,
    use_mesh: bool = True,
    grad_compress: Optional[GradCompressConfig] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    seed: int = 0,
    obs: Optional[Obs] = None,
):
    """Train ``arch`` for ``steps`` steps on ``devices`` (default: all).

    More than one device (and ``use_mesh``) builds the planned data x model
    mesh over them and places the train state by ``make_state_specs``;
    otherwise the state lives on the first device.  ``obs`` receives the
    collective census and the ``train_step`` spans (default: a fresh
    ``Obs()``, tracing off).  Returns the ``TrainLoopResult`` (one history
    record per step)."""
    devices = list(devices) if devices is not None else jax.devices()
    mesh = None
    rules = None
    if use_mesh and len(devices) > 1:
        plan = plan_mesh(len(devices), model_divisors=[s.attn.heads for s in arch.stacks if s.attn])
        mesh = make_mesh(plan["shape"], plan["axes"], devices=devices)
        rules = ShardingRules.default(mesh, arch)
        print(f"mesh: {dict(zip(plan['axes'], plan['shape']))}")
    ep_axis = "model" if (mesh is not None and any(s.moe for s in arch.stacks)) else None
    rt = Runtime(mesh=mesh, ep_axis=ep_axis, rules=rules, grad_compress=grad_compress)

    boxed = init_lm(jax.random.PRNGKey(seed), arch)
    params = unbox(boxed)
    opt = _OPTS[optimizer]()
    state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    gc = resolve_grad_compress(grad_compress, mesh)
    if grad_compress is not None and gc is None:
        print("grad-compress requested but no multi-device data axis: running uncompressed")
    if gc is not None:
        pspecs = param_specs(boxed, mesh, rules)
        state["grad_err"] = init_grad_err(params, mesh.shape[gc.axis], pspecs=pspecs, axis=gc.axis)
        print(f"grad-compress: int{gc.bits} wire over '{gc.axis}' ({gc.scale_axis} scale)")

    sched = cosine_with_warmup(lr, warmup=max(steps // 20, 1), total=steps)
    step_fn = build_train_step(arch, opt, rt, lr_schedule=sched)

    stream = TokenStream(vocab=arch.vocab, seq_len=seq, global_batch=batch, seed=seed)
    trainer = Trainer(
        step_fn,
        stream.batch,
        ckpt_dir=ckpt_dir,
        ckpt_every=ckpt_every,
        log_every=1,
        watchdog=StragglerWatchdog(),
        obs=obs,
    )
    # older checkpoints have no grad_err leaves; residuals restart from zeros
    state, start = trainer.maybe_restore(state, allow_missing=gc is not None)
    if start:
        print(f"resumed from step {start}")
    if mesh is not None:
        specs = make_state_specs(boxed, opt, mesh, rules, grad_compress=gc)
        state = jax.device_put(state, specs_to_shardings(specs, mesh))
    record_census(trainer, state, start)
    if ckpt_dir:
        install_signal_handler(trainer.emergency_save)
    return trainer.run(state, steps, start_step=start)


def record_census(trainer: Trainer, state, step: int) -> dict:
    """Compile ``trainer``'s step for ``state`` and the batch of ``step``,
    log the census of its collectives and add it to the trainer's counters
    (once per compiled step)."""
    batch = trainer.shard_batch(trainer.batch_fn(step))
    census = collective_census(trainer.step_fn.lower(state, batch).compile().as_text())
    for kind, c in census.items():
        trainer.obs.metrics.counter("train_collectives", {"kind": kind}).inc(c["count"])
        trainer.obs.metrics.counter("train_collective_bytes", {"kind": kind}).inc(c["bytes"])
    print("collectives a step: " + (", ".join(
        f"{kind} {c['count']} ({c['bytes']} B)" for kind, c in census.items() if c["count"]) or "none"))
    return census


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="CPU-runnable reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", choices=sorted(_OPTS), default="adamw")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", choices=["auto", "none"], default="auto")
    ap.add_argument(
        "--grad-compress-bits", type=int, default=0,
        help="int wire width for the data-parallel gradient all-reduce "
             "(0 = off, fp32; 8 = int8 wire with error feedback)",
    )
    ap.add_argument(
        "--grad-compress-scale", choices=["tensor", "column"], default="tensor",
        help="compressed-gradient scale granularity: one scale per leaf, or "
             "one per output column (A2Q+-style)",
    )
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    enable_compile_cache()
    grad_compress = None
    if args.grad_compress_bits:
        grad_compress = GradCompressConfig(
            bits=args.grad_compress_bits, scale_axis=args.grad_compress_scale
        )
    result = train(
        arch, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
        optimizer=args.optimizer, use_mesh=args.mesh == "auto",
        grad_compress=grad_compress, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
    )
    hist = result.history
    for rec in hist if len(hist) <= 6 else hist[:3] + hist[-3:]:
        print({k: round(v, 4) if isinstance(v, float) else v for k, v in rec.items()})
    if result.straggler_events:
        print(f"straggler events: {len(result.straggler_events)}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result.history, f, indent=1)
    first, last = result.history[0]["loss"], result.history[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f}")
    return result


if __name__ == "__main__":
    main()
