"""BENCHMARK.json keeps to the benchmark's format, and every cell, metric and
configuration it names resolves to its files by name.

Each check also runs on a copy of the benchmark to which a four-chip
training cell has been added the way a later change adds one: new files
(its configuration, its workload, the reader of a per-layer metric that
only it reports) and new entries in ``BENCHMARK.json``, its name appended
to the ``workloads`` of ``train_tok_s`` and ``train_mfu``.  The harness and
the tiny CPU cells have to take it with no edit to a file they have."""

from __future__ import annotations

import copy
import json
import re
import shutil

import pytest

from bench import core
from bench.tests import tiny

SPEC = core.benchmark_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")

ADDED_CELL, ADDED_METRIC = "added-train-tp4", "added_share.train-tp4"


def _with_added_cell(spec: dict) -> tuple[dict, dict]:
    """``spec`` with a four-chip training cell added, and the files it adds
    (path under the checkout -> text)."""
    spec = copy.deepcopy(spec)
    cfg = dict(core.load_json(tiny.DATA / "tiny-train-tp4.config.json"), name="added-tp4-train")
    workload = dict(core.load_json(tiny.DATA / "tiny-train-tp4.workload.json"),
                    why="A2Q training, tensor parallel over four chips (an added cell)")
    spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                            "file": f"bench/configs/{cfg['name']}.json", "reduced": cfg["reduced"],
                            "why": "an added configuration"})
    spec["workloads"].append({"name": ADDED_CELL, "config": cfg["name"], "traffic": "train-tp4",
                              "chips": 4, "why": workload["why"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("train_tok_s", "train_mfu"):
            m["workloads"].append(ADDED_CELL)
    spec["per_layer"].append({"name": ADDED_METRIC, "unit": "%", "better": "higher",
                              "source": "device_trace", "layer": "model step",
                              "moves": "train_tok_s", "workloads": [ADDED_CELL]})
    files = {f"bench/configs/{cfg['name']}.json": json.dumps(cfg),
             f"bench/workloads/{ADDED_CELL}.json": json.dumps(workload),
             f"bench/metrics/{ADDED_METRIC}.py": "def read(rec):\n    return None\n"}
    return spec, files


ADDED, ADDED_FILES = _with_added_cell(SPEC)


@pytest.fixture(scope="module")
def added_root(tmp_path_factory):
    """A checkout holding the benchmark with the added cell."""
    root = tmp_path_factory.mktemp("added")
    shutil.copytree(core.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for path, text in ADDED_FILES.items():
        (root / path).write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(ADDED, indent=2))
    return root


def check_top_level_keys(spec, root):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert len(json.dumps(spec)) <= 64 * 1024


def check_command_and_paths(spec, root):
    cmd, paths = spec["command"], spec["paths"]
    assert 1 <= len(paths) <= 16 and len(cmd) <= 32
    for p in paths:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (root / p).is_dir()
    for word in cmd:
        assert LINE.match(word) and not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in paths)


def check_config_entry(spec, root, cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and LINE.match(cfg["source"]) and LINE.match(cfg["why"])
    assert cfg["file"].startswith(tuple(p + "/" for p in spec["paths"]))
    body = core.load_json(root / cfg["file"])
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    assert cfg["name"] in {w["config"] for w in spec["workloads"]}


def check_cell_resolves(spec, root, cell):
    c = core.resolve_cell(cell, spec, root)
    entry = c.entry
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4) and LINE.match(entry["why"])
    assert entry["why"] == c.workload["why"]
    assert (root / "bench" / "drivers" / f"{c.workload['driver']}.py").is_file()
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        reader = core.load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in names


def check_metric_entry(spec, root, metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    e2e_names = {m["name"] for m in spec["end_to_end"]}
    if metric["name"] in e2e_names and metric in spec["end_to_end"]:
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
    cells = {w["name"] for w in spec["workloads"]}
    for w in metric.get("workloads", []):
        assert w in cells


def check_names_unique(spec, root):
    for group in ("configs", "workloads"):
        names = [x["name"] for x in spec[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


def check_setup_bound_and_four_chip_share(spec, root):
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(spec["workloads"]) // 2)


def check_run_seconds_fits_a_full_check_of_24_cells(spec, root):
    runs, cells = 2 + 14 * 24, 24
    assert runs * (spec["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200


def test_top_level_keys():
    check_top_level_keys(SPEC, core.ROOT)


def test_command_and_paths():
    check_command_and_paths(SPEC, core.ROOT)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    check_config_entry(SPEC, core.ROOT, cfg)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    check_cell_resolves(SPEC, core.ROOT, cell)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    check_metric_entry(SPEC, core.ROOT, metric)


def test_names_unique():
    check_names_unique(SPEC, core.ROOT)


def test_setup_bound_and_four_chip_share():
    check_setup_bound_and_four_chip_share(SPEC, core.ROOT)


def test_peaks_cover_the_chip():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        core.peaks("no such device")


def test_run_seconds_fits_a_full_check_of_24_cells():
    check_run_seconds_fits_a_full_check_of_24_cells(SPEC, core.ROOT)


ADDED_CHECKS = (
    [(check_top_level_keys, ()), (check_command_and_paths, ()), (check_names_unique, ()),
     (check_setup_bound_and_four_chip_share, ()), (check_run_seconds_fits_a_full_check_of_24_cells, ())]
    + [(check_config_entry, (c,)) for c in ADDED["configs"]]
    + [(check_cell_resolves, (w["name"],)) for w in ADDED["workloads"]]
    + [(check_metric_entry, (m,)) for m in ADDED["end_to_end"] + ADDED["per_layer"]])


def _case_id(case) -> str:
    check, args = case
    return "-".join([check.__name__[len("check_"):]] + [a if isinstance(a, str) else a["name"] for a in args])


@pytest.mark.parametrize("check,args", ADDED_CHECKS, ids=[_case_id(c) for c in ADDED_CHECKS])
def test_with_added_cell(added_root, check, args):
    check(core.load_json(added_root / "BENCHMARK.json"), added_root, *args)


def test_tiny_cells_take_the_added_cell(tmp_path):
    """The tiny CPU cells are built from the copy, and their metrics are the
    checked-in benchmark's: the added cell has no tiny twin."""
    _, spec = tiny.make_root(tmp_path, ADDED)
    _, now = tiny.make_root(tmp_path / "now")
    assert spec["per_layer"] == now["per_layer"]
    assert ADDED_METRIC not in {m["name"] for m in spec["per_layer"]}
    mfu = next(m for m in spec["per_layer"] if m["name"] == "train_mfu")
    assert mfu["workloads"] == ["tiny-train"]
