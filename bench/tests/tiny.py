"""Tiny cells for the CPU tests: a two-layer model of the served and the
trained architecture, run through the whole harness without the chip check
(Pallas kernels in interpret mode).

A per-layer metric of ``BENCHMARK.json`` goes to the tiny twin of each cell
that reports it, where that cell has one (``TINY_OF``); a metric none of
whose cells has a twin is left out.  So a cell added to ``BENCHMARK.json``
needs no edit here.  ``tiny-train-tp4`` stands in for a four-chip training
cell and is no cell's twin."""

from __future__ import annotations

import json
import pathlib
import shutil

from bench import core

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY_OF = {"yi6b-decode-offline": "tiny-offline", "smollm135m-train-a2q": "tiny-train"}

CELLS = {"tiny-offline": "tiny-serve", "tiny-train": "tiny-train", "tiny-train-tp4": "tiny-train-tp4"}
CHIPS = {"tiny-train-tp4": 4}
E2E = {"tiny-offline": ["output_tok_s"], "tiny-train": ["train_tok_s"],
       "tiny-train-tp4": ["train_tok_s"]}


def _reporting(metric: dict, spec: dict) -> list:
    """The cells of ``spec`` that report the per-layer ``metric``: those it
    lists, or without a list every cell that reports what it moves."""
    if "workloads" in metric:
        return metric["workloads"]
    moves = next(m for m in spec["end_to_end"] if m["name"] == metric["moves"])
    cells = [w["name"] for w in spec["workloads"]]
    return moves.get("workloads", cells)


def _per_layer(spec: dict) -> list:
    """``spec``'s per-layer metrics, each on the tiny twins of its cells."""
    out = []
    for m in spec["per_layer"]:
        twins = [TINY_OF[c] for c in _reporting(m, spec) if c in TINY_OF]
        if twins:
            out.append(dict(m, workloads=twins))
    return out


def make_root(tmp: pathlib.Path, real: dict = None) -> tuple[pathlib.Path, dict]:
    """A checkout-shaped directory holding the tiny cells' files, and the
    ``BENCHMARK.json`` object that names them, with the per-layer metrics of
    ``real`` (the repository's ``BENCHMARK.json`` by default)."""
    real = real if real is not None else core.benchmark_spec()
    for sub in ("configs", "workloads"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for cfg in set(CELLS.values()):
        shutil.copy(DATA / f"{cfg}.config.json", tmp / "bench" / "configs" / f"{cfg}.json")
    for cell in CELLS:
        shutil.copy(DATA / f"{cell}.workload.json", tmp / "bench" / "workloads" / f"{cell}.json")
    spec = {
        "configs": [{"name": c, "file": f"bench/configs/{c}.json"} for c in sorted(set(CELLS.values()))],
        "workloads": [{"name": w, "config": c, "chips": CHIPS.get(w, 1)} for w, c in CELLS.items()],
        "end_to_end": [{"name": m, "unit": "x", "workloads": [w]} for w, ms in E2E.items() for m in ms]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": _per_layer(real),
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp, spec


def run(tmp, cell: str, seed: int, seconds: float = 1.0, kind: str = "", trace: bool = False,
        **kw) -> dict:
    """One run of a tiny cell on the CPU; ``kind`` names a run of
    ``bench/control.py`` (the control or a fault) instead of a sound one."""
    import jax

    from bench import control as ctl
    from bench import run as harness

    root, spec = make_root(tmp)
    if kind:
        return ctl.run_kind(kind, cell, seed, seconds, root=root, spec=spec, devices=jax.devices(), **kw)
    return harness.run_cell(cell, seed, seconds, trace, root=root, spec=spec,
                            devices=jax.devices(), **kw)
