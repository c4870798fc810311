"""The readers of the decode host loop and of the megastep on the device
(``host_gap_ms.decode``, ``decode_tick_device_ms.decode``) on traces
recorded on a TPU v5e: one yi-6b decode megastep before the program's spans
were annotations (``data/decode_trace_events.json``, with a step record and
spans made here), one with them (``data/decode_megastep_trace_events.json``:
its ``bench.step`` and program annotations stand in for the step record and
the spans), and one smollm-135m A2Q training step
(``data/train_step_trace_events.json``), in which neither finds anything."""

from __future__ import annotations

import pathlib

import pytest

from bench import core, trace_reduce as tr

DATA_DIR = pathlib.Path(__file__).resolve().parent / "data"
DATA = DATA_DIR / "decode_trace_events.json"
TICKS = 8  # the serving cell's --decode-steps
PROGRAM_SPANS = {"engine_step", "admission", "cow_preflight", "decode_megastep",
                 "megastep_args", "megastep_sync", "replay", "admit", "prefill_chunk"}


def _reader(name):
    return core.load_module(core.BENCH_DIR / "metrics" / f"{name}.py")


def _spans(t0):
    """The program spans of one engine step starting at ``t0`` (perf_counter
    seconds), as ``Tracer.spans()`` gives them: child before parent."""
    ms = 1e-3
    return [
        ("admission", t0 + 0.01 * ms, 0.02 * ms, {"admitted": 0, "queued": 100}),
        ("cow_preflight", t0 + 0.05 * ms, 0.4 * ms, {"live": 24}),
        ("megastep_args", t0 + 0.5 * ms, 2.0 * ms, {}),
        ("megastep_sync", t0 + 3.0 * ms, 1320.0 * ms, {}),
        ("decode_megastep", t0 + 0.5 * ms, 1323.0 * ms, {"live": 24, "steps": TICKS}),
        ("replay", t0 + 1324.0 * ms, 1.0 * ms, {"tokens": 192, "released": 0}),
        ("engine_step", t0, 1326.0 * ms, {}),
    ]


def _record(trace, n_steps=1, spans=True):
    rec = core.RunRecord(cell=None)
    rec.trace = trace
    steps, all_spans = [], []
    for i in range(n_steps):
        t0 = 100.0 + 2.0 * i
        steps.append({"t0": t0 - 1e-5, "t1": t0 + 1.3261, "live": 24, "ctx": 27000,
                      "ticks": TICKS, "prefill": []})
        all_spans += _spans(t0) if spans else []
    rec.counters = {"steps": steps, "slots": 24}
    rec.spans = all_spans
    rec.trace_window = (99.0, 100.0 + 2.0 * n_steps)
    rec.window = (90.0, 200.0)
    return rec


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_events(tr.read_events(DATA))


def test_decode_tick_on_the_device(recorded):
    got = _reader("decode_tick_device_ms.decode").read(_record(recorded))
    assert got == pytest.approx(1e3 * recorded.modules["jit__megastep_fn"] / TICKS)
    assert 160 < got < 170  # 1.326 s a megastep of 8 ticks on the chip


def test_host_gap_per_megastep_and_its_split(recorded):
    rec = _record(recorded)
    got = _reader("host_gap_ms.decode").read(rec)
    assert got == pytest.approx(1e3 * (recorded.window_s - recorded.busy_s))
    assert 0 < got < 10
    split = rec.counters["host_ms_by_span"]
    # own times: each span less the spans inside it; together the step's time
    assert split["decode_megastep"] == pytest.approx(1323.0 - 2.0 - 1320.0)
    assert split["engine_step"] == pytest.approx(1326.0 - 0.02 - 0.4 - 1323.0 - 1.0)
    assert sum(split.values()) == pytest.approx(1e3 * 1.3261 + 1e-2)
    assert max(split, key=split.get) == "megastep_sync"


def test_host_gap_counts_every_traced_megastep(recorded):
    one = _reader("host_gap_ms.decode").read(_record(recorded, 1))
    three = _reader("host_gap_ms.decode").read(_record(recorded, 3))
    assert three == pytest.approx(one / 3)


def test_device_never_idle_reads_zero(recorded):
    import dataclasses

    busy = dataclasses.replace(recorded, busy_s=recorded.window_s)
    assert _reader("host_gap_ms.decode").read(_record(busy)) == 0.0


@pytest.mark.parametrize("name", ["host_gap_ms.decode", "decode_tick_device_ms.decode"])
@pytest.mark.parametrize("missing", ["trace", "megastep"])
def test_reader_finds_nothing(recorded, name, missing):
    """No trace, or a window without the megastep (its program runs, or the
    program's spans of it), reads None."""
    import dataclasses

    if missing == "trace":
        rec = _record(None)
    elif name == "host_gap_ms.decode":
        rec = _record(recorded, spans=False)
    else:
        rec = _record(dataclasses.replace(recorded, modules={"jit__unstack": 1e-6}))
    assert _reader(name).read(rec) is None


def _from_recording(name):
    """The reduced trace of a recording and a run record built from it alone:
    each ``bench.step`` a traced step (with the megastep's ticks where a
    ``decode_megastep`` annotation lies in it), each program annotation a
    span, in seconds on the profiler's clock."""
    ev = tr.read_events(DATA_DIR / name)
    red = tr.reduce_events(ev)
    spans = [(n, s / 1e9, d / 1e9, {}) for p, _, n, s, d in ev
             if p.startswith("/host") and n in PROGRAM_SPANS]
    steps = []
    for p, _, n, s, d in ev:
        if p.startswith("/host") and n == tr.STEP:
            t0, t1 = s / 1e9, (s + d) / 1e9
            mega = any(sp[0] == "decode_megastep" and t0 <= sp[1] <= t1 for sp in spans)
            steps.append({"t0": t0, "t1": t1, "ticks": TICKS if mega else 0})
    rec = core.RunRecord(cell=None, trace=red, spans=spans, counters={"steps": steps})
    rec.trace_window = (min(st["t0"] for st in steps), max(st["t1"] for st in steps))
    return red, rec


def test_readers_on_the_annotated_decode_recording():
    red, rec = _from_recording("decode_megastep_trace_events.json")
    gap = _reader("host_gap_ms.decode").read(rec)
    assert gap == pytest.approx(1e3 * (red.window_s - red.busy_s))
    assert 0 < gap < 20
    tick = _reader("decode_tick_device_ms.decode").read(rec)
    assert tick == pytest.approx(1e3 * red.modules["jit__megastep_fn"] / TICKS)
    assert 160 < tick < 170
    split = rec.counters["host_ms_by_span"]
    assert max(split, key=split.get) == "megastep_sync"
    assert {"megastep_args", "replay", "cow_preflight", "admission"} <= set(split)


@pytest.mark.parametrize("name", ["host_gap_ms.decode", "decode_tick_device_ms.decode"])
def test_readers_find_nothing_in_a_training_step(name):
    _, rec = _from_recording("train_step_trace_events.json")
    assert _reader(name).read(rec) is None


def test_recorded_idle_goes_to_program_spans():
    """With the program's spans on the profiler's clock, every idle gap of a
    megastep lies under one of them, and the gaps add up to the idle time."""
    red, _ = _from_recording("decode_megastep_trace_events.json")
    assert red.gaps and set(red.gaps) <= PROGRAM_SPANS, red.gaps
    assert sum(red.gaps.values()) == pytest.approx(red.window_s - red.busy_s)
