"""A traced run end to end on the CPU: every per-layer metric of a cell is
read, and the result carries the device's busy and window seconds and the
breakdown.  The CPU's profiler trace has no TPU plane, so the reduction is
handed the small trace recorded on the chip (``data/decode_trace_events.json``)
and the chip's peaks; what is checked is the path, not the numbers."""

from __future__ import annotations

import pathlib

import pytest

from bench import core, trace_reduce
from bench.tests import tiny

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "decode_trace_events.json"


@pytest.mark.parametrize("cell", ["tiny-offline", "tiny-train"])
def test_traced_run_reports_every_per_layer_metric(tmp_path, monkeypatch, cell):
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d, n_chips=1: trace_reduce.reduce_events(
        trace_reduce.read_events(RECORDED), n_chips))
    monkeypatch.setattr(core, "peaks", lambda kind: {"bf16_flops": 197e12, "int8_ops": 393e12,
                                                     "hbm_bytes_s": 819e9, "hbm_bytes": 16e9})
    out = tiny.run(tmp_path, cell, seed=5, seconds=4.0, trace=True)
    _, spec = tiny.make_root(tmp_path)
    want = {m["name"] for m in spec["per_layer"] if cell in m["workloads"]}
    assert want and set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["tiny-offline", "tiny-train"])
def test_reader_that_finds_nothing_is_an_error(tmp_path, monkeypatch, cell):
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d, n_chips=1: trace_reduce.reduce_events(
        trace_reduce.read_events(RECORDED), n_chips))
    monkeypatch.setattr(core, "peaks", lambda kind: {"bf16_flops": 197e12, "int8_ops": 393e12,
                                                     "hbm_bytes_s": 819e9, "hbm_bytes": 16e9})
    load = core.load_module

    def silent(path, name=None):
        mod = load(path, name)
        if path.parent.name == "metrics":
            mod.read = lambda rec: None
        return mod

    monkeypatch.setattr(core, "load_module", silent)
    with pytest.raises(RuntimeError, match="found nothing to read"):
        tiny.run(tmp_path, cell, seed=6, seconds=2.0, trace=True)
