"""Runs that ``correct`` has to refuse: the comparison's control, and the
faults a cell can have, each driven through the whole harness.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> [--kind <kind>]

Kinds:

* ``control`` (default) — the arithmetic one precision step below what the
  configuration states.  Serving (int8 KV stated): the program's own int4
  KV path (``--kv-bits 4``).  Training (bfloat16 compute stated): the plain
  reference put in the program's place with every matmul operand rounded to
  float8 (e4m3).
* ``unchanged`` (training) — a step that returns its state unchanged.
* ``half_batch`` (training) — the program's step on the first half of each
  batch, its mean taken over that half.
* ``altered_token`` (serving) — one token of every request altered where the
  scheduler records it, and fed on.

Prints the result line of ``run.py``.  The benchmark's own runs never run
this; its readings set the upper end of each limit (``PERF.md``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import core, run  # noqa: E402


class _RefOptimizer:
    """``init`` of the reference's optimizer state, as the driver asks of one."""

    @staticmethod
    def init(params):
        from bench.reference import train as ref

        return ref.init_state(params)["opt_state"]


def _reference_step(cfg: dict, rows_per_block: int):
    import jax
    import jax.numpy as jnp

    from bench.reference import train as ref

    def step(state, batch):
        new, loss, _ = ref.step(state, batch, cfg, rows_per_block=rows_per_block,
                                dot_dtype=jnp.float8_e4m3fn)
        return new, {"loss": loss}

    return jax.jit(step, donate_argnums=(0,)), _RefOptimizer()


def _unchanged_step(cfg: dict):
    import jax

    from bench.drivers.train import _program_step
    from bench.model import arch_from_config
    from repro.models.lm import lm_loss

    arch = arch_from_config(cfg)
    _, opt = _program_step(arch, cfg)
    loss = jax.jit(lambda p, b: lm_loss(p, arch, b)[0])
    return (lambda state, batch: (state, {"loss": loss(state["params"], batch)})), opt


def _half_batch_step(cfg: dict):
    from bench.drivers.train import _program_step
    from bench.model import arch_from_config

    step, opt = _program_step(arch_from_config(cfg), cfg)

    def half(state, batch):
        n = len(batch["tokens"]) // 2
        return step(state, {k: v[:n] for k, v in batch.items()})

    return half, opt


@contextlib.contextmanager
def _altered_tokens(at: int = 2):
    """Every request's ``at``-th token is replaced by the next id where the
    scheduler records it."""
    from repro.serve.scheduler import Scheduler

    record = Scheduler.record_token

    def altered(self, slot, token):
        if len(self.slots[slot].generated) == at:
            token = token + 1
        return record(self, slot, token)

    Scheduler.record_token = altered
    try:
        yield
    finally:
        Scheduler.record_token = record


def run_kind(kind: str, name: str, seed: int, seconds: float, **kw) -> dict:
    root = kw.get("root", ROOT)
    spec = kw.pop("spec", None) or core.benchmark_spec(root)
    cell = core.resolve_cell(name, spec, root)
    driver, w, cfg = cell.workload["driver"], cell.workload, cell.config
    extra: dict = {}
    guard = contextlib.nullcontext()
    if (kind, driver) == ("control", "serve"):
        extra["engine_flags"] = ["--kv-bits", "4"]
    elif (kind, driver) == ("control", "train"):
        extra["overrides"] = {"train_step": _reference_step(cfg, int(w["reference_rows_per_block"]))}
    elif (kind, driver) == ("unchanged", "train"):
        extra["overrides"] = {"train_step": _unchanged_step(cfg)}
    elif (kind, driver) == ("half_batch", "train"):
        extra["overrides"] = {"train_step": _half_batch_step(cfg)}
    elif (kind, driver) == ("altered_token", "serve"):
        guard = _altered_tokens()
    else:
        raise ValueError(f"no {kind!r} run for a {driver!r} cell")
    with guard:
        return run.run_cell(name, seed, seconds, False, spec=spec, **extra, **kw)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kind", default="control",
                    choices=("control", "unchanged", "half_batch", "altered_token"))
    args = ap.parse_args(argv)
    print(json.dumps(run_kind(args.kind, args.workload, args.seed, args.seconds)), flush=True)


if __name__ == "__main__":
    main()
