"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a small trace recorded from one yi-6b decode megastep on a TPU v5e
(``data/decode_trace_events.json``: every program run, a few operations of
each kind and the host's Python calls), and on hand-made events."""

from __future__ import annotations

import pathlib

import pytest

from bench import core, trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "decode_trace_events.json"
H, D = "/host:CPU", "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_events(tr.read_events(DATA))


def _reader(name):
    return core.load_module(core.BENCH_DIR / "metrics" / f"{name}.py")


def test_recorded_window_and_busy(recorded):
    ev = tr.read_events(DATA)
    step = [e for e in ev if e[2] == tr.STEP][0]
    assert recorded.window_s == pytest.approx(step[4] / 1e9)
    w0, w1 = step[3], step[3] + step[4]
    mega = [e for e in ev if e[1] == "XLA Modules" and e[2].startswith("jit__megastep_fn")
            and e[3] + e[4] > w0 and e[3] < w1]
    assert recorded.modules["jit__megastep_fn"] == pytest.approx(
        sum(min(e[3] + e[4], w1) - max(e[3], w0) for e in mega) / 1e9)
    assert 0.99 < recorded.busy_s / recorded.window_s <= 1.0


def test_recorded_kernels_told_apart(recorded):
    import re

    pool = re.compile(_reader("paged_attention_roofline.decode").POOL)
    weight = re.compile(_reader("int_matmul_roofline.decode").WEIGHT)
    kinds = {}
    for op in recorded.ops:
        if "tpu_custom_call" not in op:
            continue
        kinds[op.split(" = ")[0]] = "attn" if pool.search(op) else ("mm" if weight.search(op) else "?")
    assert kinds["%checkpoint.67"] == "attn"
    assert {kinds[k] for k in ("%checkpoint.69", "%checkpoint.70", "%checkpoint.71")} == {"mm"}
    assert "?" not in kinds.values()


def test_recorded_gap_goes_to_the_host_call(recorded):
    top = recorded.top_gaps(1)[0]
    assert top[0] == "$engine.py:997 megastep" and top[1] > 0
    assert sum(recorded.gaps.values()) == pytest.approx(recorded.window_s - recorded.busy_s)


def _synthetic():
    ms = 1e6
    return [
        (H, "python 1", tr.STEP, 0.0, 100 * ms),
        (H, "python 1", "host.prepare", 40 * ms, 20 * ms),
        (H, "python 1", "host.inner", 45 * ms, 10 * ms),
        (D, "XLA Modules", "jit_a(1)", 0.0, 40 * ms),
        (D, "XLA Modules", "jit_b(2)", 60 * ms, 50 * ms),   # runs past the window
        (D, "XLA Ops", "%while.1 = (s32[]) while(s32[] %x)", 0.0, 40 * ms),
        (D, "XLA Ops", '%k.1 = f32[8,16]{1,0} custom-call(s8[16,16]{1,0} %w), custom_call_target="tpu_custom_call"',
         10 * ms, 25 * ms),
        (D, "XLA Ops", "%fusion.2 = f32[8] fusion(f32[8] %y)", 60 * ms, 30 * ms),
    ]


def test_synthetic_busy_self_time_and_gaps():
    r = tr.reduce_events(_synthetic())
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.08)           # 0-40 ms and 60-100 ms
    assert r.op_seconds(r"while") == pytest.approx(0.015)   # 40 ms less its 25 ms kernel
    assert r.op_seconds(r"tpu_custom_call") == pytest.approx(0.025)
    assert r.gaps == {"host.inner": pytest.approx(0.02)}
    assert r.top_ops(1)[0] == ["fusion f32[8] fusion.2", pytest.approx(0.03)]


@pytest.mark.parametrize("drop", ["step", "device"])
def test_trace_without_steps_or_device_refused(drop):
    ev = [e for e in _synthetic() if not (drop == "step" and e[2] == tr.STEP)
          and not (drop == "device" and e[0] == D)]
    with pytest.raises(ValueError):
        tr.reduce_events(ev)
