"""Training loop: jitted step, metrics, checkpointing, watchdog, emergency
save.  Works identically on 1 CPU device (examples/tests) and on a production
mesh (launch/train.py passes shardings + Runtime).

Each step runs inside a ``train_step`` span (args ``step``) of the trainer's
``obs.Tracer``: from the call to the loss being ready, on ``perf_counter``
and, when the tracer is enabled, as a profiler annotation.  The default
tracer is disabled and costs nothing; the span is host-side and changes
nothing in the compiled step."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Obs
from repro.train import checkpoint as ckpt
from repro.train.elastic import StragglerWatchdog

__all__ = ["Trainer", "TrainLoopResult"]


@dataclasses.dataclass
class TrainLoopResult:
    state: Any
    history: list
    straggler_events: tuple


class Trainer:
    """Drives ``step_fn(state, batch) -> (state, metrics)`` over a stateless
    batch source (``batch_fn(step) -> dict``).  ``obs`` carries the tracer
    the ``train_step`` spans go to and the metrics registry the launcher
    records into (default: ``Obs()``, tracing off)."""

    def __init__(
        self,
        step_fn: Callable,
        batch_fn: Callable[[int], dict],
        *,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 100,
        keep: int = 3,
        log_every: int = 10,
        donate: bool = True,
        watchdog: Optional[StragglerWatchdog] = None,
        shard_batch: Optional[Callable[[dict], Any]] = None,
        obs: Optional[Obs] = None,
    ):
        self.batch_fn = batch_fn
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.keep = keep
        self.log_every = log_every
        self.watchdog = watchdog or StragglerWatchdog()
        self.obs = obs or Obs()
        self.shard_batch = shard_batch or (lambda b: {k: jnp.asarray(v) for k, v in b.items()})
        self.step_fn = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
        self._last_state = None

    def maybe_restore(self, state, allow_missing: bool = False):
        """Resume from the latest valid checkpoint if one exists (the data
        stream is stateless, so the step index fully restores the run).

        ``allow_missing`` tolerates state leaves absent from the checkpoint
        (e.g. resuming with gradient compression newly enabled: the
        ``grad_err`` residuals restart from zeros)."""
        if self.ckpt_dir is None:
            return state, 0
        latest = ckpt.latest_step(self.ckpt_dir)
        if latest is None:
            return state, 0
        tree, step = ckpt.restore(self.ckpt_dir, state, allow_missing=allow_missing)
        return tree, int(step)

    def emergency_save(self):
        if self.ckpt_dir is not None and self._last_state is not None:
            step = int(jax.device_get(self._last_state["step"]))
            ckpt.save(self.ckpt_dir, self._last_state, step, keep=self.keep)

    def run(self, state, n_steps: int, start_step: Optional[int] = None) -> TrainLoopResult:
        history = []
        start = start_step if start_step is not None else int(jax.device_get(state["step"]))
        for i in range(start, start + n_steps):
            batch = self.shard_batch(self.batch_fn(i))
            t0 = time.perf_counter()
            with self.obs.trace.span("train_step", {"step": i}):
                state, metrics = self.step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self._last_state = state
            self.watchdog.observe(i, dt)
            if i % self.log_every == 0 or i == start + n_steps - 1:
                rec = {k: float(jax.device_get(v)) for k, v in metrics.items()}
                rec.update(step=i, step_time=dt)
                history.append(rec)
            if self.ckpt_dir is not None and (i + 1) % self.ckpt_every == 0:
                ckpt.save(self.ckpt_dir, state, i + 1, keep=self.keep)
        if self.ckpt_dir is not None:
            ckpt.save(self.ckpt_dir, state, start + n_steps, keep=self.keep)
        return TrainLoopResult(state, history, self.watchdog.events)
