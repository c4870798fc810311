"""``correct`` on the tiny cells, through the whole harness on the CPU: sound
runs pass, and the control and each fault a cell can have are refused.

The tiny cells' limits (``data/*.workload.json``) were set from CPU readings
of this size.  Serving, at float32 compute with the reference's keys and
values kept as int8 codes as the cache keeps them: sound runs read a widest
gap of 0 (eight runs), the int4 KV control 0.027 to 0.033, an altered token
0.38 to 0.57; limit 0.005.  Training: sound runs read loss gaps of 0.0008
to 0.0014, parameter-change gaps of 0.05 to 0.11 and a median first-gradient
difference of 0.046 to 0.053; a half batch reads loss gaps of 0.008 and
0.032 and a median first-gradient difference of 0.70 and 0.75; the float8
control and an unchanged state read a change gap of 1.0."""

from __future__ import annotations

import pytest

from bench.tests import tiny


@pytest.mark.parametrize("cell", ["tiny-offline", "tiny-train"])
def test_sound_run_is_correct(tmp_path, cell):
    out = tiny.run(tmp_path, cell, seed=2**33 + 21)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == set(tiny.E2E[cell]) | {"setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,kind", [
    ("tiny-offline", "control"),
    ("tiny-offline", "altered_token"),
    ("tiny-train", "control"),
    ("tiny-train", "unchanged"),
    ("tiny-train", "half_batch"),
])
def test_refused(tmp_path, cell, kind):
    out = tiny.run(tmp_path, cell, seed=22, kind=kind)
    assert not out["correct"], out["checks"]
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed and "window_compiles" not in failed
