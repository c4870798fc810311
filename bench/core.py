"""What every cell of the benchmark shares: files found by name, the device
check, the compile cache and compile counter, and the result line.

Nothing here knows a cell, a configuration or a metric by name: each lives in
a file of its own (``configs/<config>.json``, ``workloads/<cell>.json``,
``drivers/<driver>.py``, ``metrics/<metric>.py``) that the harness finds by
the name ``BENCHMARK.json`` gives it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Any, Callable, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import a file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name or f"bench_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    entry: dict          # the BENCHMARK.json workloads entry
    workload: dict       # workloads/<name>.json
    config: dict         # configs/<config>.json
    end_to_end: list     # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list      # BENCHMARK.json per_layer entries this cell reports

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _reports(metric: dict, cell: str, cell_e2e: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:  # a per-layer metric without a list: every cell of its metric
        return metric["moves"] in cell_e2e
    return True


def resolve_cell(name: str, spec: dict, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``spec`` (a parsed ``BENCHMARK.json``) with its
    workload file ``bench/workloads/<name>.json`` and its configuration file."""
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    workload = load_json(root / "bench" / "workloads" / f"{name}.json")
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / cfgs[entry["config"]]["file"])
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, entry, workload, config, e2e, per_layer)


# -- the device ---------------------------------------------------------------


class NoChip(SystemExit):
    """Raised where JAX finds no accelerator or too few chips."""


def require_chips(n: int):
    """The devices the cell runs on; exits non-zero where JAX finds no TPU or
    fewer than ``n`` of them.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform {devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise NoChip(f"bench: the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def peaks(device_kind: str) -> dict:
    """One chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


# -- compilation ----------------------------------------------------------------


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.cache``: ``JAX_COMPILATION_CACHE_DIR`` where the
    environment sets it, else ``.jax_cache`` at the root of the checkout),
    keeping every program, however quickly it compiled."""
    import jax

    from repro.launch.cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs a process obtains, compiled or loaded from the
    persistent cache, through ``jax.monitoring``; read around the window."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == self.BACKEND:  # wraps the cache lookup: hits count too
            self.compiles += 1
            self.compile_s += duration
            self.names.append(kw.get("fun_name", "?"))

    def total(self) -> int:
        return self.compiles


# -- the run record handed to per-layer readers ----------------------------------


@dataclasses.dataclass
class RunRecord:
    """What a run saw, for the per-layer readers in ``metrics/``.

    ``spans``: program spans ``(name, t0_s, dur_s, args)`` on ``perf_counter``;
    ``counters``: the program's and the benchmark's counts; ``trace``: the
    reduced profiler trace (``trace_reduce.Reduced``) or None; ``work``: the
    operations and bytes of the traced window by kernel (``work.py``);
    ``peaks``: the rates of the cell's chips taken together (``bf16_flops``,
    ``int8_ops``, ``hbm_bytes_s``) and one chip's capacity ``hbm_bytes``."""

    cell: Cell
    window: tuple = (0.0, 0.0)
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    requests: list = dataclasses.field(default_factory=list)
    trace: Any = None
    trace_window: tuple = (0.0, 0.0)
    work: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Check:
    """One compared number, its limit, and which side of it passes."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit}


def print_checks(checks: list) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAILED'}",
              file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)
