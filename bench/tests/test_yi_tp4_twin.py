"""The yi-6b four-chip training cell's tiny twin, through the whole harness
on four virtual CPU devices.

``tiny-yi-train-tp4`` (``data/tiny-yi-train-tp4.*.json``) has Yi-6B's
shape at a tiny width: 2 layers, 16 query heads over 4 KV heads (each
device holds 4 query heads and their one KV head), an untied head, rope
theta 5e6, d_ff 344 (86 columns a device, not a multiple of 8), vocabulary
256.  It is added to the tiny cells' ``BENCHMARK.json`` as a four-chip
cell, the way ``yi6b-train-tp4`` is added to the real one.  One child
process (``XLA_FLAGS`` has to be set before JAX starts; JAX's compile cache
in a temporary directory lets the runs share their compiled programs)
makes, on the mesh that ``bench/drivers/train.py`` plans (data 1 x model 4):

* a sound run, which has to be correct;
* the same first steps on one device from the same weights and rows,
  whose losses have to be the mesh's within ``LOSS_TOL``;
* the float8 control and the half-batch fault (``bench/control.py``),
  which ``correct`` has to refuse;
* the first gradient of a float32 twin of the program on the mesh and of
  the reference, which have to agree leaf by leaf: the bfloat16 program's
  gap on the head's activation quantizer (``head.aq.log2_scale``) is its
  rounding, not the mesh's arithmetic.

The twin's limits (``data/tiny-yi-train-tp4.workload.json``) are the
cell's kinds of limit, set from CPU readings of this size over six seeds:

======================  ===============  ============  ============  =====
number                  sound            float8        half batch    limit
======================  ===============  ============  ============  =====
loss_gap                0.00004-0.00028  0.0005-0.0014 0.0032-0.011  0.001
grad_norm_gap_median    0.0009-0.0014    0.66-0.84     0.31-0.41     0.02
change_norm_gap_median  0.0008-0.0020    0.69-0.88     0.014-0.028   0.1
grad_diff_median        0.021-0.026      0.66-0.84     0.87-0.97     0.2
======================  ===============  ============  ============  =====

An unchanged state reads 0.98 to 1.0 on the last three.  The worst leaf's
change gap (``change_norm_gap``: sound 0.047-0.084 here, 0.29-9.8 at the
cell's widths on the chip, always on a leaf whose gradient is near zero)
is recorded but not a limit."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL, SEED = "tiny-yi-train-tp4", 2**31 + 977

# The first steps' losses on four devices against one: the same bfloat16
# step, partitioned, rounds its partial sums in another order.  Measured
# (CPU, four seeds) 0.000011 to 0.00011 on losses of about 5.55; half of a
# batch left out moves them 0.0044 to 0.010 from the reference.
LOSS_TOL = 0.002
# float32 program against the float32 reference, per leaf, relative to the
# larger of the leaf's reference gradient norm and the median leaf's.
F32_GRAD_TOL = 1e-4


def _root(tmp: pathlib.Path):
    from bench.tests import tiny

    root, spec = tiny.make_root(tmp)
    for kind in ("config", "workload"):
        shutil.copy(tiny.DATA / f"{CELL}.{kind}.json", root / "bench" / f"{kind}s" / f"{CELL}.json")
    spec["configs"].append({"name": CELL, "file": f"bench/configs/{CELL}.json"})
    spec["workloads"].append({"name": CELL, "config": CELL, "chips": 4})
    spec["end_to_end"].append({"name": "train_tok_s", "unit": "x", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, spec


def _counters(root) -> dict:
    from bench import core

    return core.load_json(root / "chiprun_out" / "bench" / f"{CELL}-{SEED}-0" / "counters.json")


def _one_device_losses(cfg: dict, rows: int, seq: int, steps: int) -> list:
    import jax
    import jax.numpy as jnp

    from bench.drivers.train import _program_step
    from bench.model import arch_from_config, make_params
    from bench.traffic import tokens as feed

    step, opt = _program_step(arch_from_config(cfg), cfg)
    params = make_params(cfg, SEED, deployed=False)
    state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    losses = []
    for i in range(steps):
        state, m = step(state, feed.batch(SEED, i, rows, seq, cfg["vocab_size"]))
        losses.append(float(m["loss"]))
    return losses


def _float32_gradient_gaps(cfg: dict, rows: int, seq: int) -> dict:
    """Per leaf, ``|g_prog - g_ref| / max(|g_ref|, median leaf's |g_ref|)``
    of the first gradient, the program at float32 on the mesh."""
    import jax
    import numpy as np

    from bench.drivers import train as drv
    from bench.model import arch_from_config, make_params
    from bench.reference import train as ref
    from bench.traffic import tokens as feed
    from repro.models.lm import Runtime, lm_loss

    cfg = dict(cfg, compute_dtype="float32")
    arch = arch_from_config(cfg)
    mesh, rules, _ = drv._layout(arch, jax.devices())
    _, opt = drv._program_step(arch, cfg, mesh, rules)
    state_at, batch_at = drv._shardings(arch, opt, mesh, rules, rows, seq)
    params = make_params(cfg, SEED, deployed=False, shardings=state_at["params"])
    b = feed.batch(SEED, 0, rows, seq, cfg["vocab_size"])
    rt = Runtime(mesh=mesh, rules=rules)
    with jax.default_matmul_precision("highest"):
        g_prog = jax.jit(jax.grad(lambda p, b: lm_loss(p, arch, b, rt=rt)[0]))(
            params, jax.device_put(b, batch_at))
        _, g_ref = jax.jit(lambda p, b: ref.grads(p, cfg, b["tokens"], b["targets"], rows))(params, b)
    flat = jax.tree_util.tree_flatten_with_path(g_ref)[0]
    prog = dict(jax.tree_util.tree_flatten_with_path(g_prog)[0])
    ref_norm = {p: float(np.linalg.norm(np.asarray(x))) for p, x in flat}
    med = float(np.median(list(ref_norm.values())))
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(prog[p]) - np.asarray(x)))
            / max(ref_norm[p], med) for p, x in flat}


def _child(tmp: str) -> None:
    import jax

    from bench import control, core, run as harness

    assert len(jax.devices()) == 4, jax.devices()
    root, spec = _root(pathlib.Path(tmp))
    w = core.load_json(root / "bench" / "workloads" / f"{CELL}.json")
    cfg = core.load_json(root / "bench" / "configs" / f"{CELL}.json")
    out = {"sound": harness.run_cell(CELL, SEED, 0.3, False, root=root, spec=spec, devices=jax.devices())}
    out["counters"] = _counters(root)
    out["one_device_losses"] = _one_device_losses(cfg, w["batch"], w["seq"], w["check_steps"])
    for kind in ("control", "half_batch"):
        out[kind] = control.run_kind(kind, CELL, SEED, 0.3, root=root, spec=spec, devices=jax.devices())
    out["f32_grad_gaps"] = _float32_gradient_gaps(cfg, w["batch"], w["seq"])
    print(json.dumps(out))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("yi-tp4")
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    p = subprocess.run([sys.executable, __file__, str(tmp)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_on_the_mesh_is_correct(runs):
    assert runs["sound"]["correct"], runs["sound"]["checks"]
    assert runs["counters"]["mesh"] == {"data": 1, "model": 4}


def test_losses_equal_one_device(runs):
    mesh, one = runs["counters"]["losses"], runs["one_device_losses"]
    assert len(mesh) == len(one) == 3
    assert max(abs(a - b) for a, b in zip(mesh, one)) <= LOSS_TOL, (mesh, one)


@pytest.mark.parametrize("kind", ["control", "half_batch"])
def test_refused(runs, kind):
    out = runs[kind]
    assert not out["correct"], out["checks"]
    failed = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert failed and "window_compiles" not in failed


def test_float32_program_on_the_mesh_has_the_reference_gradient(runs):
    gaps = runs["f32_grad_gaps"]
    assert "['head']['aq']['log2_scale']" in gaps
    assert max(gaps.values()) <= F32_GRAD_TOL, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]


if __name__ == "__main__":
    _child(sys.argv[1])
