"""A training cell on four devices, through the whole harness on the CPU.

The tiny cell ``tiny-train-tp4`` (2 layers, d 64, 4 heads and 4 KV heads,
untied head) runs in a child process that has four virtual CPU devices
(``XLA_FLAGS`` has to be set before JAX starts): once traced on all four,
once on the first device alone.  On four the driver plans the launcher's
mesh, data 1 x model 4, and the run has to be correct; its first steps'
losses have to be the one-device run's, and ``train_mfu`` has to read
against four chips' peak.  As in ``test_traced_run.py`` the reduction is
handed a trace recorded on the chip, since the CPU's trace has no TPU
plane."""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "train_step_trace_events.json"
V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9}
CELL, SEED = "tiny-train-tp4", 2**33 + 61

# The first steps' losses on four devices against one: the same bfloat16
# step, partitioned, rounds its partial sums in another order.  Measured
# (CPU, four seeds): 0.00029 to 0.00056 on losses of about 5.56, each
# side 0.0008 to 0.0021 from the float32 reference; half of a batch left
# out moves a tiny cell's loss by 0.008 or more (``test_correct.py``).
LOSS_TOL = 0.002


def _child(tmp: str) -> None:
    import jax

    from bench import core, run as harness, trace_reduce
    from bench.tests import tiny

    assert len(jax.devices()) == 4, jax.devices()
    trace_reduce.reduce_dir = lambda d, n_chips=1: trace_reduce.reduce_events(
        trace_reduce.read_events(RECORDED), n_chips)
    core.peaks = lambda kind: dict(V5E)
    seen = {}
    load = core.load_module

    def keep_record(path, name=None):
        mod = load(path, name)
        if path.name == "train_mfu.py":
            read = mod.read
            mod.read = lambda rec: seen.setdefault("train_mfu", (rec, read(rec)))[1]
            seen["reader"] = read
        return mod

    core.load_module = keep_record
    root, spec = tiny.make_root(pathlib.Path(tmp))
    for m in spec["per_layer"]:  # what the training twin reports, the four-device cell reports
        if "tiny-train" in m["workloads"]:
            m["workloads"].append(CELL)
    four = harness.run_cell(CELL, SEED, 2.0, True, root=root, spec=spec, devices=jax.devices())
    one = harness.run_cell(CELL, SEED, 1.0, False, root=root, spec=spec, devices=jax.devices()[:1])
    rec, mfu = seen["train_mfu"]
    placed = _placed_weights(core.load_json(root / "bench" / "configs" / f"{CELL}.json"), jax.devices())
    counters = {n: core.load_json(root / "chiprun_out" / "bench" / f"{CELL}-{SEED}-{t}" / "counters.json")
                for n, t in (("four", 1), ("one", 0))}
    print(json.dumps({
        "four": four, "one": one,
        "mesh": {n: c["mesh"] for n, c in counters.items()},
        "losses": {n: c["losses"] for n, c in counters.items()},
        "peaks": rec.peaks, "train_mfu": mfu,
        "train_mfu_one_chip_peak": seen["reader"](dataclasses.replace(rec, peaks=dict(V5E))),
        "placed": placed,
    }))


def _placed_weights(cfg: dict, devices) -> dict:
    """The driver's weights on the four devices: whether each leaf came out
    of ``make_params`` with the sharding the state specs give it, and the
    head's shard on one device."""
    import jax

    from bench.drivers import train
    from bench.model import arch_from_config, make_params

    arch = arch_from_config(cfg)
    mesh, rules, _ = train._layout(arch, devices)
    _, opt = train._program_step(arch, cfg, mesh, rules)
    state_at, _ = train._shardings(arch, opt, mesh, rules, 4, 32)
    params = make_params(cfg, SEED, deployed=False, shardings=state_at["params"])
    same = jax.tree.map(lambda x, s: x.sharding.is_equivalent_to(s, x.ndim), params, state_at["params"])
    return {"as_specified": all(jax.tree.leaves(same)),
            "head_v_shard": list(params["head"]["v"].addressable_shards[0].data.shape)}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, __file__, str(tmp_path_factory.mktemp("four"))],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_device_run_is_correct(runs):
    assert runs["four"]["correct"], runs["four"]["checks"]
    assert runs["one"]["correct"], runs["one"]["checks"]


def test_mesh_is_the_launchers(runs):
    assert runs["mesh"] == {"four": {"data": 1, "model": 4}, "one": None}


def test_weights_made_into_their_shardings(runs):
    assert runs["placed"] == {"as_specified": True, "head_v_shard": [64, 64]}  # d 64 x vocab 256 / 4


def test_losses_equal_one_device(runs):
    four, one = runs["losses"]["four"], runs["losses"]["one"]
    assert len(four) == len(one) == 3
    assert max(abs(a - b) for a, b in zip(four, one)) <= LOSS_TOL, (four, one)


def test_train_mfu_over_four_chips(runs):
    assert runs["peaks"] == {"bf16_flops": 4 * 197e12, "int8_ops": 4 * 393e12,
                             "hbm_bytes_s": 4 * 819e9, "hbm_bytes": 16e9}
    assert runs["train_mfu"] > 0
    assert runs["train_mfu"] == pytest.approx(runs["train_mfu_one_chip_peak"] / 4, rel=1e-12)
    assert runs["four"]["metrics"]["train_mfu"]["value"] == runs["train_mfu"]


if __name__ == "__main__":
    _child(sys.argv[1])
