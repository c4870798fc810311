"""Tiny cells for the CPU tests: a two-layer model of the served and the
trained architecture, run through the whole harness without the chip check
(Pallas kernels in interpret mode)."""

from __future__ import annotations

import json
import pathlib
import shutil

from bench import core

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY_OF = {"yi6b-decode-offline": "tiny-offline", "smollm135m-train-a2q": "tiny-train"}

CELLS = {"tiny-offline": "tiny-serve", "tiny-train": "tiny-train"}
E2E = {"tiny-offline": ["output_tok_s"], "tiny-train": ["train_tok_s"]}


def make_root(tmp: pathlib.Path) -> tuple[pathlib.Path, dict]:
    """A checkout-shaped directory holding the tiny cells' files, and the
    ``BENCHMARK.json`` object that names them."""
    for sub in ("configs", "workloads"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    for cfg in set(CELLS.values()):
        shutil.copy(DATA / f"{cfg}.config.json", tmp / "bench" / "configs" / f"{cfg}.json")
    for cell in CELLS:
        shutil.copy(DATA / f"{cell}.workload.json", tmp / "bench" / "workloads" / f"{cell}.json")
    spec = {
        "configs": [{"name": c, "file": f"bench/configs/{c}.json"} for c in sorted(set(CELLS.values()))],
        "workloads": [{"name": w, "config": c, "chips": 1} for w, c in CELLS.items()],
        "end_to_end": [{"name": m, "unit": "x", "workloads": [w]} for w, ms in E2E.items() for m in ms]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": [dict(m, workloads=[TINY_OF[w] for w in m["workloads"]])
                      for m in core.benchmark_spec()["per_layer"]],
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
