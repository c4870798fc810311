"""BENCHMARK.json keeps to the benchmark's format, and every cell, metric and
configuration it names resolves to its files by name."""

from __future__ import annotations

import json
import re

import pytest

from bench import core

SPEC = core.benchmark_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_command_and_paths():
    cmd, paths = SPEC["command"], SPEC["paths"]
    assert 1 <= len(paths) <= 16 and len(cmd) <= 32
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (core.ROOT / p).is_dir()
    for word in cmd:
        assert LINE.match(word) and not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in paths)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and LINE.match(cfg["source"]) and LINE.match(cfg["why"])
    assert cfg["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    body = core.load_json(core.ROOT / cfg["file"])
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    assert cfg["name"] in {w["config"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = core.resolve_cell(cell, SPEC)
    entry = c.entry
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4) and LINE.match(entry["why"])
    assert entry["why"] == c.workload["why"]
    assert (core.BENCH_DIR / "drivers" / f"{c.workload['driver']}.py").is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        reader = core.load_module(core.BENCH_DIR / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in names


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    e2e_names = {m["name"] for m in SPEC["end_to_end"]}
    if metric["name"] in e2e_names and metric in SPEC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert LINE.match(metric["layer"]) and metric["moves"] in e2e_names
        if "roofline" in metric["name"]:
            assert metric["name"].endswith("_roofline") or "_roofline." in metric["name"]
            assert metric["unit"] == "%"
    for w in metric.get("workloads", []):
        assert w in CELLS


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_setup_bound_and_four_chip_share():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_peaks_cover_the_chip():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        core.peaks("no such device")


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs, cells = 2 + 14 * 24, 24
    assert runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200
