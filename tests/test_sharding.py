"""Sharding rules (divisibility fallback) + real multi-device execution in an
8-fake-device subprocess (tests must not set XLA_FLAGS in-process)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.dist.sharding import ShardingRules, resolve_pspec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        out = 1
        for v in self.shape.values():
            out *= v
        return out


def test_resolve_divisibility_fallback():
    mesh = _FakeMesh({"data": 16, "model": 16})
    arch = get_arch("smollm-135m")
    rules = ShardingRules.default(mesh, arch)
    # 9 heads don't divide 16 -> replicated; embed 576 FSDPs over data=16
    spec = resolve_pspec(("embed", "heads"), (576, 576), mesh, rules)
    assert spec == P("data", None)
    # d_ff=1536 shards over model
    spec = resolve_pspec(("embed", "mlp"), (576, 1536), mesh, rules)
    assert spec == P("data", "model")


def test_resolve_unit_counts_respected():
    mesh = _FakeMesh({"data": 16, "model": 16})
    arch = get_arch("command-r-35b")
    rules = ShardingRules.default(mesh, arch)
    # fused (d, H*Dh) = (8192, 8192): heads=64 divisible by 16 -> sharded
    assert resolve_pspec(("embed", "heads"), (8192, 8192), mesh, rules) == P("data", "model")
    # kv fused dim: kv_heads=8 not divisible by 16 -> replicated on dim 1
    assert resolve_pspec(("embed", "kv_heads"), (8192, 1024), mesh, rules) == P("data", None)


def test_no_mesh_axis_reused_across_dims():
    mesh = _FakeMesh({"data": 4, "model": 4})
    rules = ShardingRules(
        rules={"a": ("model",), "b": ("model",)}, unit_counts={}
    )
    spec = resolve_pspec(("a", "b"), (16, 16), mesh, rules)
    assert spec == P("model", None)  # second dim can't reuse 'model'


def test_batch_axes_multi_pod():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = ShardingRules.default(mesh, None)
    assert rules.rules["batch"] == ("pod", "data")
    spec = resolve_pspec(("batch", None, None), (256, 4096, 1), mesh, rules)
    assert spec == P(("pod", "data"), None, None)


def _run_subprocess(body: str, n_dev: int = 8, env: dict = None) -> str:
    # every script builds its meshes with the Auto-axis helper
    code = "from repro.dist.sharding import make_mesh\n" + textwrap.dedent(body)
    env = dict(os.environ, **(env or {}))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The same reduced model + batch gives the same loss on a (2, 4) mesh as
    on one device — the distribution layer must not change the math."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.dist.sharding import ShardingRules, param_specs
        from repro.models import Runtime, init_lm
        from repro.models.steps import build_train_step
        from repro.nn.module import unbox
        from repro.optim.optimizers import adamw

        arch = reduced(get_arch("yi-6b"))
        key = jax.random.PRNGKey(0)
        boxed = init_lm(key, arch)
        params = unbox(boxed)
        opt = adamw()
        batch = {
            "tokens": jnp.asarray(np.random.default_rng(0).integers(0, arch.vocab, (8, 32)), jnp.int32),
            "targets": jnp.asarray(np.random.default_rng(1).integers(0, arch.vocab, (8, 32)), jnp.int32),
        }
        state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}

        # single device
        step1 = jax.jit(build_train_step(arch, opt, Runtime()))
        _, m1 = step1(jax.tree.map(lambda x: x, state), batch)

        # (data=2, model=4) mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = ShardingRules.default(mesh, arch)
        rt = Runtime(mesh=mesh, rules=rules)
        stepm = jax.jit(build_train_step(arch, opt, rt))
        with mesh:
            _, m2 = stepm(state, batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) < 1e-3, (l1, l2)
        print("OK", l1, l2)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_moe_ep_shard_map_matches_local():
    """MoE with experts sharded over 'model' == single-device dispatch."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import MoEConfig, QuantConfig
        from repro.nn import moe
        from repro.nn.module import unbox

        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, capacity_factor=8.0)
        q = QuantConfig(mode="none")
        key = jax.random.PRNGKey(0)
        p = unbox(moe.init_moe(key, 8, cfg, q))
        x = jax.random.normal(key, (4, 8, 8), jnp.float32)
        local = moe.apply_moe(p, x, cfg, q, compute_dtype=jnp.float32)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            ep = jax.jit(lambda p, x: moe.apply_moe(p, x, cfg, q, ep_axis="model",
                                                    mesh=mesh, compute_dtype=jnp.float32))(p, x)
        err = float(jnp.abs(local - ep).max())
        assert err < 1e-4, err
        print("OK", err)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_moe_ep_over_both_axes_matches_local():
    """Serving layout: experts sharded over (model, data), 1 expert/shard."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp
        from repro.configs.base import MoEConfig, QuantConfig
        from repro.nn import moe
        from repro.nn.module import unbox

        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, capacity_factor=8.0)
        q = QuantConfig(mode="none")
        key = jax.random.PRNGKey(0)
        p = unbox(moe.init_moe(key, 8, cfg, q))
        x = jax.random.normal(key, (4, 8, 8), jnp.float32)
        local = moe.apply_moe(p, x, cfg, q, compute_dtype=jnp.float32)
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            ep = jax.jit(lambda p, x: moe.apply_moe(p, x, cfg, q, ep_axis=("model", "data"),
                                                    mesh=mesh, compute_dtype=jnp.float32))(p, x)
        err = float(jnp.abs(local - ep).max())
        assert err < 1e-4, err
        print("OK", err)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_compressed_psum_error_feedback():
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import compressed_psum

        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)

        def f(xs, err):
            return compressed_psum(xs, "data", err, bits=8)

        g = jax.jit(jax.shard_map(f, mesh=mesh,
                                  in_specs=(P("data"), P("data")),
                                  out_specs=(P("data"), P("data")), check_vma=False))
        errs = jnp.zeros_like(x)
        total, errs = g(x, errs)
        exact = jnp.sum(x, axis=0, keepdims=True)
        rel = float(jnp.abs(total[0] - exact[0]).max() / jnp.abs(exact).max())
        assert rel < 0.05, rel
        # error feedback: residual equals what compression dropped
        assert float(jnp.abs(errs).max()) > 0
        print("OK", rel)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_compressed_grad_training_tracks_uncompressed():
    """20 training steps on an 8-device data mesh: int8-compressed gradient
    reduction (error feedback on) stays within tolerance of the fp32 path,
    both residual trees are live, and the residual pair survives a
    checkpoint save/restore cycle (plus allow_missing restore from an
    uncompressed checkpoint)."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import TokenStream
        from repro.dist.collectives import GradCompressConfig
        from repro.dist.sharding import ShardingRules, param_specs
        from repro.models import Runtime, init_lm
        from repro.models.steps import build_train_step
        from repro.nn.module import unbox
        from repro.optim.optimizers import adamw
        from repro.train import checkpoint as ckpt
        from repro.train.state import init_grad_err

        arch = reduced(get_arch("smollm-135m"))
        mesh = make_mesh((8,), ("data",))
        rules = ShardingRules.default(mesh, arch)
        params = unbox(init_lm(jax.random.PRNGKey(0), arch))
        boxed = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch))
        pspecs = param_specs(boxed, mesh, rules)
        opt = adamw()
        stream = TokenStream(vocab=arch.vocab, seq_len=32, global_batch=8)

        def run(rt, extra):
            state = {"params": params, "opt_state": opt.init(params),
                     "step": jnp.zeros((), jnp.int32), **extra}
            step = jax.jit(build_train_step(arch, opt, rt,
                                            lr_schedule=lambda s: jnp.float32(2e-3)))
            losses = []
            for i in range(20):
                batch = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            return losses, state

        base, _ = run(Runtime(mesh=mesh, rules=rules), {})
        gc = GradCompressConfig(bits=8, axis="data")
        err0 = init_grad_err(params, 8, pspecs=pspecs, axis="data")
        comp, st = run(Runtime(mesh=mesh, rules=rules, grad_compress=gc),
                       {"grad_err": err0})
        # both learn, trajectories track (error feedback keeps the int8
        # path from drifting)
        assert base[-1] < base[0] - 0.5 and comp[-1] < comp[0] - 0.5
        diff = max(abs(a - b) for a, b in zip(base, comp))
        assert diff < 0.05, (diff, base[-1], comp[-1])
        local_nz = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(st["grad_err"]["local"]))
        server_nz = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(st["grad_err"]["server"]))
        assert local_nz > 0 and server_nz > 0

        # the residual pair round-trips through a checkpoint
        d = tempfile.mkdtemp()
        ckpt.save(d, st, 20)
        like = {"params": params, "opt_state": opt.init(params),
                "step": jnp.zeros((), jnp.int32),
                "grad_err": init_grad_err(params, 8, pspecs=pspecs, axis="data")}
        restored, step_no = ckpt.restore(d, like)
        assert step_no == 20
        for a, b in zip(jax.tree.leaves(restored["grad_err"]),
                        jax.tree.leaves(st["grad_err"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # enabling compression mid-run: an uncompressed checkpoint restores
        # with allow_missing and the residuals restart from zeros
        d2 = tempfile.mkdtemp()
        no_gc = {k: v for k, v in st.items() if k != "grad_err"}
        ckpt.save(d2, no_gc, 5)
        restored2, _ = ckpt.restore(d2, like, allow_missing=True)
        assert sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(restored2["grad_err"])) == 0.0
        try:
            ckpt.restore(d2, like)
            raise SystemExit("expected KeyError")
        except KeyError:
            pass
        print("OK", diff)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_compressed_grad_training_on_tp_mesh():
    """Same contract on a (data=2, model=4) mesh: the compressed reduction
    must coexist with tensor parallelism (per-column scales here)."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp
        from repro.configs import get_arch, reduced
        from repro.data.synthetic import TokenStream
        from repro.dist.collectives import GradCompressConfig
        from repro.dist.sharding import ShardingRules, param_specs
        from repro.models import Runtime, init_lm
        from repro.models.steps import build_train_step
        from repro.nn.module import unbox
        from repro.optim.optimizers import adamw
        from repro.train.state import init_grad_err

        arch = reduced(get_arch("smollm-135m"))
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = ShardingRules.default(mesh, arch)
        params = unbox(init_lm(jax.random.PRNGKey(0), arch))
        boxed = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch))
        pspecs = param_specs(boxed, mesh, rules)
        opt = adamw()
        stream = TokenStream(vocab=arch.vocab, seq_len=32, global_batch=8)

        def run(rt, extra):
            state = {"params": params, "opt_state": opt.init(params),
                     "step": jnp.zeros((), jnp.int32), **extra}
            step = jax.jit(build_train_step(arch, opt, rt,
                                            lr_schedule=lambda s: jnp.float32(2e-3)))
            losses = []
            for i in range(12):
                batch = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        base = run(Runtime(mesh=mesh, rules=rules), {})
        gc = GradCompressConfig(bits=8, scale_axis="column", axis="data")
        comp = run(Runtime(mesh=mesh, rules=rules, grad_compress=gc),
                   {"grad_err": init_grad_err(params, 2, pspecs=pspecs, axis="data")})
        diff = max(abs(a - b) for a, b in zip(base, comp))
        assert diff < 0.05, diff
        print("OK", diff)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_decode_with_kv_sharded_cache_matches_unsharded():
    """Decode with the KV-cache head dim sharded over `model` (kv_heads=4 on
    a 4-way model axis) compiles and matches the single-device decode
    numerics — the cache_specs change must not alter the math."""
    out = _run_subprocess(
        """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_arch, reduced
        from repro.dist.sharding import ShardingRules, cache_specs, param_specs
        from repro.models import Runtime, init_cache, init_lm
        from repro.models.steps import build_serve_step
        from repro.nn.module import unbox

        arch = reduced(get_arch("yi-6b"))
        s0 = arch.stacks[0]
        arch = dataclasses.replace(
            arch,
            stacks=(dataclasses.replace(s0, attn=dataclasses.replace(s0.attn, kv_heads=4)),)
            + arch.stacks[1:],
        )
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = ShardingRules.default(mesh, arch)
        params = unbox(init_lm(jax.random.PRNGKey(0), arch))
        cache = init_cache(arch, 8, 32)
        cspecs = cache_specs(cache, mesh, rules)
        assert cspecs["0"]["attn"]["k"][3] == "model", cspecs["0"]["attn"]["k"]

        tokens = jnp.asarray(np.random.default_rng(0).integers(0, arch.vocab, (8, 1)), jnp.int32)
        pos = jnp.zeros((), jnp.int32)

        # single device reference
        logits_ref, _ = build_serve_step(arch, Runtime())(params, tokens, cache, pos)

        pspecs = param_specs(jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch)), mesh, rules)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        rt = Runtime(mesh=mesh, rules=rules)
        with mesh:
            step = jax.jit(
                build_serve_step(arch, rt),
                in_shardings=(sh(pspecs), NamedSharding(mesh, P("data")),
                              sh(cspecs), NamedSharding(mesh, P())),
                out_shardings=(None, sh(cspecs)),
            )
            logits, new_cache = step(params, tokens, cache, pos)
        err = float(jnp.abs(logits.astype(jnp.float32) - logits_ref.astype(jnp.float32)).max())
        assert err < 1e-2, err
        # the cache was actually written at pos 0
        assert int(new_cache["0"]["attn"]["kpos"][0, 0, 0]) == 0
        print("OK", err)
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_elastic_reshard_restore():
    """Checkpoint saved unsharded restores onto a live mesh with resharding."""
    out = _run_subprocess(
        """
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        d = tempfile.mkdtemp()
        ckpt.save(d, tree, 7)
        mesh = make_mesh((4, 2), ("data", "model"))
        sh = {"w": NamedSharding(mesh, P("data", "model"))}
        restored, step = ckpt.restore(d, tree, shardings=sh)
        assert step == 7
        assert restored["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
        print("OK")
        """
    )
    assert "OK" in out


# -- collective census of the launcher's compiled step (dist/census.py) ---------

# A tiny yi-6b twin on the launcher's path: 16 heads over 4 KV heads, d 128,
# d_ff 344, trained 2 steps of 4 x 32 tokens at 2 layers on four devices
# (mesh data 1 x model 4) with tracing on and off, at 3 layers on four and at
# 2 on one.  The census the launcher records is read back from its counters;
# the HLO text it was taken from is kept as well, and the step's compiles are
# counted from JAX's compile events.  JAX's compile cache (a temporary
# directory) gives the untraced run the traced run's program; a cache hit
# still sends its compile event, so the count sees every compile asked for.
_CENSUS_RUNS = """
import dataclasses, json, jax
from jax import monitoring
from repro.configs import get_arch, reduced
from repro.launch import train as launch
from repro.obs import Obs

compiles = []
monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(kw.get("fun_name"))
    if event == "/jax/core/compile/backend_compile_duration" else None)
texts = []
count = launch.collective_census
launch.collective_census = lambda text: (texts.append(text), count(text))[1]
base = reduced(get_arch("yi-6b"), head_dim=8)
def arch(layers):
    s = base.stacks[0]
    attn = dataclasses.replace(s.attn, heads=16, kv_heads=4)
    return dataclasses.replace(base, d_model=128, stacks=(dataclasses.replace(
        s, attn=attn, d_ff=344, count=layers),))
out = {}
for name, layers, n_dev, trace in [("L2-traced", 2, 4, True), ("L2", 2, 4, False),
                                   ("L3", 3, 4, False), ("one", 2, 1, False)]:
    obs = Obs(trace=trace)
    compiles.clear()
    launch.train(arch(layers), steps=2, batch=4, seq=32, devices=jax.devices()[:n_dev], obs=obs)
    out[name] = {"counters": obs.metrics.snapshot(), "text": texts[-1],
                 "spans": [[n, a] for n, _, _, a in obs.trace.spans()],
                 "step_compiles": compiles.count("jit(train_step)")}
print(json.dumps(out))
"""
_B, _T, _D = 4, 32, 128


@pytest.fixture(scope="module")
def census_runs(tmp_path_factory):
    cache = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path_factory.mktemp("census_cache")),
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    return json.loads(_run_subprocess(_CENSUS_RUNS, n_dev=4, env=cache).strip().splitlines()[-1])


def _census(run: dict) -> dict:
    out = {}
    for key, v in run["counters"].items():
        name, kind = key.rstrip("}").split("{kind=")
        out.setdefault(kind, {})["count" if name == "train_collectives" else "bytes"] = v["value"]
    return out


def test_census_tp4_step_all_reduces_per_layer(census_runs):
    """Tensor parallel 4 puts all-reduces, and only all-reduces, into the
    step, and each layer adds the same bytes: 8 all-reduces of one
    float32 (B, T, d) activation (the row-parallel o and down projections
    forward, o again in the block's recompute, the input gradients of the
    column-parallel q, k, v, gate and in), plus per-channel l1 norms of
    the row-parallel weights and scalar partial sums, under one
    activation's worth.  Outside the layers the step adds the head's input
    gradient and the vocabulary-split loss's reductions: under four
    activations."""
    c2, c3 = (_census(census_runs[n]) for n in ("L2", "L3"))
    assert set(c2) == {"all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all"}
    assert all(c2[k] == {"count": 0, "bytes": 0} for k in c2 if k != "all-reduce")
    per_layer = c3["all-reduce"]["bytes"] - c2["all-reduce"]["bytes"]
    act = _B * _T * _D * 4
    assert per_layer // act == 8 and per_layer % act < act // 8, per_layer
    assert 0 < c2["all-reduce"]["bytes"] - 2 * per_layer < 4 * act


def test_census_one_device_step_is_empty(census_runs):
    c = _census(census_runs["one"])
    assert c and all(v == {"count": 0, "bytes": 0} for v in c.values())


def test_census_counts_each_compiled_step_once(census_runs):
    """The counters hold one compiled step's census, not one per step run."""
    from repro.dist.census import collective_census

    run = census_runs["L2"]
    assert _census(run) == {k: {"count": float(v["count"]), "bytes": float(v["bytes"])}
                            for k, v in collective_census(run["text"]).items()}


@pytest.mark.parametrize("run", ["L2-traced", "L2", "L3", "one"])
def test_launcher_compiles_the_step_once(census_runs, run):
    """The census's compile before the loop is the one the loop runs: the
    trainer's jitted step asks for no second compile."""
    assert census_runs[run]["step_compiles"] == 1


def test_train_step_span_only_when_tracing(census_runs):
    assert census_runs["L2"]["spans"] == []
    assert census_runs["L2-traced"]["spans"] == [["train_step", {"step": 0}], ["train_step", {"step": 1}]]


def test_tracing_leaves_the_compiled_step_unchanged(census_runs):
    assert census_runs["L2-traced"]["text"] == census_runs["L2"]["text"]
    assert _census(census_runs["L2-traced"]) == _census(census_runs["L2"])


_SYNTHETIC_HLO = """HloModule step

%add (x: f32[], y: f32[]) -> f32[] {
  ROOT %s = f32[] add(f32[] %x, f32[] %y)
}

%cond (p: (s32[], bf16[8,128])) -> pred[] {
  %p = (s32[], bf16[8,128]) parameter(0)
  %constant.3 = s32[]{:T(128)} constant(3)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %constant.3), direction=LT
}

%body (p: (s32[], bf16[8,128])) -> (s32[], bf16[8,128]) {
  %p = (s32[], bf16[8,128]) parameter(0)
  %x = bf16[8,128] get-tuple-element(%p), index=1
  %ar = bf16[8,128]{1,0:T(8,128)} all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%add
  %ags = (bf16[8,32], bf16[8,128]) all-gather-start(%x), dimensions={1}
  %agd = bf16[8,128] all-gather-done(%ags)
  ROOT %t = (s32[], bf16[8,128]) tuple(%i, %ar)
}

ENTRY %main (a: bf16[8,128], b: f32[16]) -> bf16[8,128] {
  %a = bf16[8,128] parameter(0)
  %b = f32[16] parameter(1)
  %w = (s32[], bf16[8,128]) while(%t0), condition=%cond, body=%body
  %ar2 = (f32[16], f32[]) all-reduce(%b, %c), to_apply=%add
  %cps = (f32[16], f32[16], u32[], u32[]) collective-permute-start(%b), source_target_pairs={{0,1}}
  %cpd = f32[16] collective-permute-done(%cps)
  ROOT %r = bf16[8,128] get-tuple-element(%w), index=1
}
"""


def test_census_counts_loop_trips_and_async_pairs_once():
    """A ``while`` body counts once per trip (its condition's ``i < 3``),
    an async pair once at its ``-done``, a tuple result by all its
    elements; a known trip count, where the text gives one, wins."""
    from repro.dist.census import collective_census

    c = collective_census(_SYNTHETIC_HLO)
    assert c["all-reduce"] == {"count": 3 + 1, "bytes": 3 * 8 * 128 * 2 + 16 * 4 + 4}
    assert c["all-gather"] == {"count": 3, "bytes": 3 * 8 * 128 * 2}
    assert c["collective-permute"] == {"count": 1, "bytes": 16 * 4}
    assert c["reduce-scatter"] == c["all-to-all"] == {"count": 0, "bytes": 0}
    known = _SYNTHETIC_HLO.replace("body=%body", 'body=%body, backend_config={"known_trip_count":{"n":"5"}}')
    assert collective_census(known)["all-reduce"]["count"] == 5 + 1
