"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (its workload file, its
configuration file and its driver, each found by name), builds the system
under test on the chip, warms every shape the window uses, measures for
``--seconds`` and prints one JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the ``breakdown`` of the traced part of the window), then
``checks``: each number compared for ``correct`` with its limit.  The same
numbers end standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` takes a
profiler trace of a few seconds of the window and reports the cell's
per-layer metrics, each read by ``bench/metrics/<name>.py``.  A share of a
peak or a roofline is taken over the cell's chips: the readers get the
rates of all of them together, and device time averaged over them.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import core  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver is given, and what it fills in for the harness."""

    cell: core.Cell
    seed: int
    seconds: float
    trace: bool
    out_dir: pathlib.Path
    counter: core.CompileCounter
    devices: list
    engine_flags: list = dataclasses.field(default_factory=list)
    overrides: dict = dataclasses.field(default_factory=dict)
    setup: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    record: core.RunRecord = None
    window_start: float = 0.0
    window_compiles: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    trace_dir: pathlib.Path = None
    t_start: float = T_START

    def begin_window(self) -> None:
        self.window_start = time.perf_counter()
        self._compiles0 = self.counter.total()

    def end_window(self) -> None:
        self.window_compiles = self.counter.total() - self._compiles0
        if self.window_compiles:
            core.log(f"compiled in the window: {self.counter.names[self._compiles0:]}")

    def read_memory(self) -> None:
        self.device = core.device_info(self.devices)


def _metric_values(ctx: Context, result: dict) -> dict:
    units = {m["name"]: m["unit"] for m in ctx.cell.end_to_end + ctx.cell.per_layer}
    out = {}
    if not ctx.trace:
        vals = dict(result["e2e"], setup_s=ctx.window_start - ctx.t_start)
        for m in ctx.cell.end_to_end:
            if m["name"] not in vals:
                raise KeyError(f"the driver reported no {m['name']!r}")
            out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        return out
    for m in ctx.cell.per_layer:
        reader = core.load_module(core.BENCH_DIR / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx.record)
        if value is None:
            raise RuntimeError(f"per-layer metric {m['name']!r} found nothing to read in this "
                               f"traced run of {ctx.cell.name!r}, which lists it")
        out[m["name"]] = {"value": value, "unit": units[m["name"]]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, root: pathlib.Path = ROOT,
             spec: dict = None, devices=None, engine_flags=(), overrides=None) -> dict:
    """One run of a cell; returns the result object.  ``devices`` stands in
    for the chip check and leaves the compile cache off (tests run a tiny
    cell on the CPU this way)."""
    spec = spec if spec is not None else core.benchmark_spec(root)
    cell = core.resolve_cell(name, spec, root)
    if devices is None:
        devices = core.require_chips(cell.chips)
        core.enable_compile_cache()
    counter = core.CompileCounter()
    out_dir = root / "chiprun_out" / "bench" / f"{name}-{seed}-{int(trace)}"
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace, out_dir=out_dir,
                  counter=counter, devices=list(devices), engine_flags=list(engine_flags),
                  overrides=dict(overrides or {}))
    ctx.record = core.RunRecord(cell=cell, counters=ctx.counters)
    driver = core.load_module(core.BENCH_DIR / "drivers" / f"{cell.workload['driver']}.py")
    core.log(f"{name}: seed {seed}, {seconds} s window, trace {int(trace)}, "
             f"{devices[0].device_kind} x {len(devices)}")
    result = driver.run(ctx)
    if not ctx.device:
        ctx.read_memory()
    ctx.counters["window_compiles"] = ctx.window_compiles
    ctx.counters.update({f"setup.{k}": v for k, v in ctx.setup.items()})
    core.log(f"set-up {ctx.window_start - ctx.t_start:.3f} s ({ctx.setup}), "
             f"compiles in the window {ctx.window_compiles}")
    device = dict(ctx.device)
    breakdown = None
    if trace:
        from bench import trace_reduce, work

        if ctx.trace_dir is None:
            raise RuntimeError("traced run took no trace")
        red = trace_reduce.reduce_dir(ctx.trace_dir, n_chips=len(devices))
        shutil.rmtree(ctx.trace_dir)  # tens of MB; what the readers need is in ``red``
        ctx.counters["trace_ops"] = sorted(([k[:400], v] for k, v in red.ops.items()),
                                           key=lambda kv: -kv[1])[:40]
        ctx.record.trace = red
        chip = core.peaks(devices[0].device_kind)  # rates add up over chips, capacity does not
        ctx.record.peaks = dict(chip, **{k: chip[k] * len(devices)
                                         for k in ("bf16_flops", "int8_ops", "hbm_bytes_s")})
        ctx.record.work = work.window_work(ctx.record)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.top_gaps(10)}
    metrics = _metric_values(ctx, result)
    checks = result["checks"]
    core.print_checks(checks)
    out = {"correct": all(c.ok for c in checks), "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: c.as_json() for c in checks}
    with open(out_dir / "counters.json", "w") as f:
        json.dump(ctx.counters, f, indent=1, default=float)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out, allow_nan=True), flush=True)


if __name__ == "__main__":
    main()
