"""Traffic generators: the same seed gives the same inputs, and every seed
gives a cell the same sizes (so the set-up warms the same shapes)."""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from bench import core
from bench.traffic import requests as traffic
from bench.traffic import tokens

SPEC = core.benchmark_spec()
SERVE = [w["name"] for w in SPEC["workloads"]
         if core.resolve_cell(w["name"], SPEC).workload["driver"] == "serve"]
SEEDS = [0, 7, 2**31 + 5, 3_000_000_017]


def _sizes(reqs):
    return Counter((len(r.prompt), r.max_new, 0 if r.context is None else len(r.context))
                   for r in reqs)


@pytest.mark.parametrize("spec", [
    {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024, "levels": 12},
    {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 8, "max": 384, "levels": 16},
    {"dist": "uniform", "min": 1024, "max": 2048, "levels": 12},
])
def test_length_levels(spec):
    lv = traffic.length_levels(spec)
    assert len(lv) == spec["levels"] and lv == sorted(lv)
    assert all(spec["min"] <= x <= spec["max"] for x in lv)
    if spec["dist"] == "lognormal":
        mid = lv[len(lv) // 2 - 1: len(lv) // 2 + 1]
        assert min(mid) <= spec["median"] <= max(mid)


@pytest.mark.parametrize("cell", SERVE)
def test_offline_backlog_fixed_sizes(cell):
    w = core.resolve_cell(cell, SPEC).workload
    if w["traffic"] != "offline":
        pytest.skip("not an offline cell")
    chunk, vocab, slots = 256, 64000, w["slots"]
    runs = [traffic.offline_backlog({**w, "prefill_chunk": chunk}, s, vocab, slots) for s in SEEDS]
    again = traffic.offline_backlog({**w, "prefill_chunk": chunk}, SEEDS[0], vocab, slots)
    a, b = runs[0], again
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    for init, back in runs[1:]:
        assert _sizes(init) == _sizes(runs[0][0]) and _sizes(back) == _sizes(runs[0][1])
        assert [(len(r.prompt), r.max_new) for r in back] == \
            [(len(r.prompt), r.max_new) for r in runs[0][1]]
    assert not np.array_equal(runs[0][0][0].prompt, runs[1][0][0].prompt) or \
        len(runs[0][0][0].prompt) != len(runs[1][0][0].prompt)
    init, back = runs[0]
    assert len(init) == slots and len(back) == w["backlog"]
    for r in init:
        assert len(r.context) % chunk == 0
        assert len(r.prompt) + len(r.context) + r.max_new <= w["max_seq"]
    shapes = {tuple(traffic.prefill_shapes(
        [len(r.prompt) + (0 if r.context is None else len(r.context)) for r in i + k], chunk))
        for i, k in runs}
    assert len(shapes) == 1


@pytest.mark.parametrize("lengths,chunk,want", [
    ([256, 512], 256, [256]),
    ([100, 356, 612], 256, [100, 256]),
    ([5, 7, 300], 16, [5, 7, 12, 16]),
])
def test_prefill_shapes(lengths, chunk, want):
    assert traffic.prefill_shapes(lengths, chunk) == want


@pytest.mark.parametrize("seed", SEEDS)
def test_token_batches(seed):
    a = tokens.batch(seed, 3, 4, 16, 1000)
    b = tokens.batch(seed, 3, 4, 16, 1000)
    c = tokens.batch(seed, 4, 4, 16, 1000)
    assert np.array_equal(a["tokens"], b["tokens"]) and not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert len({r.tobytes() for r in np.concatenate([a["tokens"], c["tokens"]])}) == 8
