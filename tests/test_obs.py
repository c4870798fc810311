"""Observability layer: span tracing, metrics registry, headroom telemetry,
and the cluster-wide snapshot merge."""

import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import QuantConfig
from repro.kernels import ops
from repro.kernels.paged_attention import compute_block_pages, kv_block_range
from repro.models.lm import Runtime, init_lm
from repro.nn.module import unbox
from repro.obs import (
    NULL_SPAN, MetricsRegistry, Obs, Tracer, merge_snapshots, percentile,
)
from repro.obs.headroom import engine_headroom, static_headroom_report
from repro.serve.engine import PagedServeEngine, deploy_params

KEY = jax.random.PRNGKey(0)
KW = dict(batch=2, max_seq=64, block_size=4, prefill_chunk=4)


def _params(arch):
    return unbox(init_lm(KEY, arch))


# -- tracer ------------------------------------------------------------------


def test_span_nesting_child_before_parent():
    tr = Tracer()
    with tr.span("parent"):
        with tr.span("child"):
            pass
    names = [name for _, name, _, _, _ in tr.events]
    assert names == ["child", "parent"], "append-on-exit orders child first"
    (child, parent) = tr.spans("child")[0], tr.spans("parent")[0]
    # containment: the child starts no earlier and ends no later
    assert parent[1] <= child[1]
    assert child[1] + child[2] <= parent[1] + parent[2] + 1e-9


def test_disabled_tracer_is_null_span_identity(monkeypatch):
    import repro.obs.trace as trace_mod

    def no_annotation(*a, **k):
        raise AssertionError("a disabled tracer entered a profiler annotation")

    monkeypatch.setattr(trace_mod, "TraceAnnotation", no_annotation)
    tr = Tracer(enabled=False)
    s1 = tr.span("a", {"k": 1})
    s2 = tr.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN, "one shared no-op span"
    with s1:
        pass
    tr.instant("i", {"x": 2})
    assert tr.events == [], "disabled tracer records nothing"
    assert s1.dur_s == 0.0


def test_chrome_export_schema(tmp_path):
    tr = Tracer(pid=3, tid=7)
    with tr.span("outer", {"uid": 1}):
        tr.instant("mark")
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 2
    by_ph = {e["ph"]: e for e in evs}
    assert set(by_ph) == {"X", "i"}
    x, i = by_ph["X"], by_ph["i"]
    assert x["name"] == "outer" and x["args"] == {"uid": 1}
    assert x["dur"] >= 0 and x["ts"] >= 0  # microseconds from tracer origin
    assert i["s"] == "t" and "dur" not in i
    assert all(e["pid"] == 3 and e["tid"] == 7 for e in evs)


# spans on the profiler's clock: run in a child process, so that no profiler
# trace another test left running in this worker can get in the way
_PROFILED = r"""
import glob, json, sys
import jax
from jax.profiler import ProfileData
from repro.obs import Tracer

tr = Tracer()
d = sys.argv[1]
jax.profiler.start_trace(d)
with tr.span("decode_megastep", {"live": 3, "steps": 8}):
    with tr.span("megastep_sync"):
        pass
filled = {"tokens": 0, "released": 0}
with tr.span("replay", filled):
    filled.update(tokens=5, released=1)
tr.instant("emit", {"uid": 1})
jax.profiler.stop_trace()
pd = ProfileData.from_file(glob.glob(d + "/**/*.xplane.pb", recursive=True)[0])
out = [[plane.name, line.name, ev.name, dict(ev.stats)]
       for plane in pd.planes for line in plane.lines for ev in line.events
       if ev.name in ("decode_megastep", "megastep_sync", "replay", "emit")]
print(json.dumps({"profiled": out, "recorded": [e[:2] + e[4:] for e in tr.events]}))
"""


@pytest.fixture(scope="module")
def profiled_spans(tmp_path_factory):
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _PROFILED, str(tmp_path_factory.mktemp("profile"))],
                         capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name,stats", [
    ("decode_megastep", {"live": 3, "steps": 8}),
    ("megastep_sync", {}),
    ("replay", {"tokens": 5, "released": 1}),  # args filled in while the span ran
])
def test_enabled_span_lands_on_the_profiler_trace(profiled_spans, name, stats):
    hits = [e for e in profiled_spans["profiled"] if e[2] == name]
    assert len(hits) == 1, profiled_spans["profiled"]
    plane, line, _, got = hits[0]
    assert plane.startswith("/host") and line.startswith("python")
    assert got == stats
    # the perf_counter record is the one it always was
    assert ["X", name, stats or None] in profiled_spans["recorded"]


def test_instant_makes_no_annotation(profiled_spans):
    assert not [e for e in profiled_spans["profiled"] if e[2] == "emit"]
    assert ["i", "emit", {"uid": 1}] in profiled_spans["recorded"]


def test_tracer_clear_resets_origin_and_events():
    tr = Tracer()
    tr.instant("before")
    tr.clear()
    assert tr.events == []
    tr.instant("after")
    ts = tr.to_chrome()["traceEvents"][0]["ts"]
    assert 0 <= ts < 1e6, "timestamps rebase onto the cleared origin"


# -- metrics -----------------------------------------------------------------


def test_percentile_nearest_rank():
    assert percentile([], 99) == 0.0
    assert percentile([5.0], 50) == 5.0
    vals = [1.0, 2.0, 3.0, 4.0]
    # nearest-rank: rank = ceil(q/100 * n), 1-indexed
    assert percentile(vals, 50) == 2.0
    assert percentile(vals, 75) == 3.0
    assert percentile(vals, 99) == 4.0
    # order-independent
    assert percentile([4.0, 1.0, 3.0, 2.0], 50) == 2.0


def test_registry_snapshot_and_load_roundtrip():
    m = MetricsRegistry()
    m.counter("c", {"k": "v"}).inc(3)
    m.gauge("g").set(1.5)
    m.histogram("h").observe(2.0)
    m.histogram("h").observe(4.0)
    snap = m.snapshot()
    assert snap["c{k=v}"] == {"type": "counter", "value": 3}
    assert snap["g"] == {"type": "gauge", "value": 1.5}
    assert snap["h"]["values"] == [2.0, 4.0]
    m2 = MetricsRegistry()
    m2.load(snap)
    assert m2.snapshot() == snap
    assert m2.histogram("h").percentile(99) == 4.0


def test_registry_type_mismatch_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_merge_snapshots_associative_and_commutative():
    def mk(c, g, h):
        m = MetricsRegistry()
        m.counter("reqs").inc(c)
        m.gauge("peak").set(g)
        for v in h:
            m.histogram("lat").observe(v)
        return m.snapshot()

    a, b, c = mk(1, 5.0, [1.0]), mk(2, 3.0, [2.0, 9.0]), mk(4, 7.0, [0.5])
    ab_c = merge_snapshots(merge_snapshots(a, b), c)
    a_bc = merge_snapshots(a, merge_snapshots(b, c))
    ba = merge_snapshots(b, a)

    def canon(s):
        return {k: (sorted(v["values"]) if "values" in v else v["value"])
                for k, v in s.items()}

    assert canon(ab_c) == canon(a_bc), "merge is associative"
    assert canon(merge_snapshots(a, b)) == canon(ba), "merge is commutative"
    assert ab_c["reqs"]["value"] == 7, "counters add"
    assert ab_c["peak"]["value"] == 7.0, "gauges merge by max"
    assert sorted(ab_c["lat"]["values"]) == [0.5, 1.0, 2.0, 9.0], "histograms concat"


# -- accumulator headroom ----------------------------------------------------


def test_acc_probe_pow2_witness():
    """Exactly predictable accumulator magnitude through the fused path:
    q8 = all-ones (32, 4), unit scales, x = 4.0 broadcast -> every output
    accumulator is exactly 32 * 4 = 128 against a 16-bit bound of 32767."""
    from repro.nn.linear import acc_probe_scope, apply_linear

    cfg = QuantConfig(mode="a2q", weight_bits=8, act_bits=8, acc_bits=16)
    params = {
        "q8": jnp.ones((32, 4), jnp.int8),
        "s8": jnp.ones((4,), jnp.float32),
        "aq": {"log2_scale": jnp.zeros((), jnp.float32)},
    }
    x = jnp.full((1, 32), 4.0, jnp.float32)
    samples = []
    with acc_probe_scope(samples):
        y = apply_linear(params, x, cfg, int_forward=True, site="witness",
                         compute_dtype=jnp.float32)
    assert len(samples) == 1
    rec = samples[0]
    assert rec["site"] == "witness"
    assert rec["acc_max"] == 128, rec
    assert rec["acc_bits"] == 16 and rec["bound"] == 2 ** 15 - 1
    # the kernel really computed 4 * 32 per column (scale 1.0 end to end)
    np.testing.assert_allclose(np.asarray(y), 128.0)


def test_acc_probe_inactive_without_scope():
    from repro.nn.linear import _ACTIVE_ACC_PROBE

    assert _ACTIVE_ACC_PROBE == [], "no probe scope leaks across tests"


def test_static_headroom_all_layers_within_guarantee():
    arch = reduced(get_arch("yi-6b"))
    dep = deploy_params(_params(arch), arch.quant)
    report = static_headroom_report(dep, arch.quant)
    assert report, "deployed tree has q8 leaves"
    for rec in report:
        assert 0.0 <= rec["utilization"] < 1.0, rec
        assert rec["l1_max"] <= rec["l1_budget"], rec
        assert rec["site"]


def test_engine_headroom_gauges_and_zero_violations():
    arch = reduced(get_arch("yi-6b"))
    dep = deploy_params(_params(arch), arch.quant)
    e = PagedServeEngine(arch, dep, rt=Runtime(int_forward=True), **KW)
    hr = engine_headroom(e, seq=4)
    assert hr["violations"] == 0
    assert 0.0 < hr["util_max"] < 1.0
    assert hr["observed_sites"] > 0, "eager probe hit at least one fused site"
    assert 0.0 < hr["observed_frac_max"] <= hr["util_max"] + 1e-9, \
        "observed magnitude cannot exceed the static worst case"
    snap = e.obs.metrics.snapshot()
    assert snap["acc_headroom_violations"]["value"] == 0
    assert any(k.startswith("acc_headroom_utilization{") for k in snap)
    assert any(k.startswith("acc_observed_max{") for k in snap)


# -- engine integration ------------------------------------------------------


def _prompts(arch, n=3, rng=None):
    rng = rng or np.random.default_rng(0)
    return [rng.integers(0, arch.vocab, (int(L),)).astype(np.int32)
            for L in rng.integers(4, 9, size=n)]


def test_traced_engine_spans_and_parity():
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    plain = PagedServeEngine(arch, params, **KW)
    traced = PagedServeEngine(arch, params, obs=Obs(trace=True),
                              decode_steps=2, **KW)
    prompts = _prompts(arch)
    want = plain.generate(prompts, max_new=4)
    got = traced.generate(prompts, max_new=4)
    assert got == want, "tracing is observation only"
    names = traced.obs.trace.span_names()
    assert {"submit", "admit", "prefill_chunk", "block_alloc",
            "decode_megastep", "emit"} <= names, names
    # one submit and one emit instant per request
    assert len(traced.obs.trace.instants("submit")) == len(prompts)
    assert len(traced.obs.trace.instants("emit")) == len(prompts)
    # every admit span carries its request uid
    for _, _, _, args in traced.obs.trace.spans("admit"):
        assert "uid" in args and "slot" in args


def test_traced_megastep_host_loop_spans():
    arch = reduced(get_arch("yi-6b"))
    e = PagedServeEngine(arch, _params(arch), obs=Obs(trace=True), decode_steps=2,
                         rt=Runtime(decode_kernel=True), **KW)
    lens_at_megastep = []
    megastep = e.megastep

    def recorded():
        lens_at_megastep.append(e.cache.lens.copy())
        return megastep()

    e.megastep = recorded
    prompts = _prompts(arch, n=3)
    e.generate(prompts, max_new=5)
    tr = e.obs.trace
    steps = tr.spans("engine_step")
    mega = tr.spans("decode_megastep")
    assert steps and mega
    # every host-loop span lies inside an engine step, and the argument
    # uploads and the sync inside a megastep
    inside = lambda sp, outer: any(o[1] <= sp[1] and sp[1] + sp[2] <= o[1] + o[2] for o in outer)
    for name in ("admission", "cow_preflight", "decode_megastep", "replay"):
        assert tr.spans(name) and all(inside(sp, steps) for sp in tr.spans(name)), name
    for name in ("megastep_args", "megastep_sync"):
        assert len(tr.spans(name)) == len(mega) and all(inside(sp, mega) for sp in tr.spans(name))
    assert sum(a["admitted"] for *_, a in tr.spans("admission")) == len(prompts)
    assert sum(a["tokens"] for *_, a in tr.spans("replay")) == e.stats["decode_tokens"]
    assert sum(a["released"] for *_, a in tr.spans("replay")) == len(prompts)
    # the paged decode kernel's walk at each megastep's first tick: every
    # row at its length with that tick's token, counted by the helper that
    # bounds the kernel's walk
    kp = e.cache.pools["0"]["attn"]["kp"]
    _, _, bs, kv, width = kp.shape
    mb = e.cache.max_blocks_per_seq
    pages = compute_block_pages(bs, kv, width, kp.dtype, mb, False)
    assert len(lens_at_megastep) == len(mega)
    for lens, (*_, args) in zip(lens_at_megastep, mega):
        first, end = kv_block_range(lens + 1, pages * bs)
        assert args["kv_blocks_walked"] == int((end - first).sum())
        assert args["kv_blocks_grid"] == e.batch * -(-mb // pages)
        assert 0 < args["kv_blocks_walked"] <= args["kv_blocks_grid"]


def test_untraced_engine_records_no_events():
    arch = reduced(get_arch("yi-6b"))
    e = PagedServeEngine(arch, _params(arch), **KW)
    e.generate(_prompts(arch, n=2), max_new=3)
    assert e.obs.trace.events == []
    # ...but request-latency histograms still populate (metrics are cheap)
    assert e.obs.metrics.histogram("request_latency_s").count == 2


def test_metrics_snapshot_unifies_engine_and_cache_stats():
    arch = reduced(get_arch("yi-6b"))
    e = PagedServeEngine(arch, _params(arch), **KW)
    prompts = _prompts(arch)
    e.generate(prompts, max_new=4)
    snap = e.metrics_snapshot()
    assert snap["serve_decode_tokens"]["value"] == e.stats["decode_tokens"]
    assert snap["serve_prefill_tokens"]["value"] == e.stats["prefill_tokens"]
    assert snap["requests_completed"]["value"] == len(prompts)
    assert snap["kv_peak_blocks"]["value"] == e.cache.peak_blocks > 0
    assert len(snap["request_latency_s"]["values"]) == len(prompts)
    assert len(snap["request_ttft_s"]["values"]) == len(prompts)
    assert any(k.startswith("jit_cache_size{fn=") for k in snap)


def test_reset_stats_single_path_clears_everything():
    arch = reduced(get_arch("yi-6b"))
    e = PagedServeEngine(arch, _params(arch), obs=Obs(trace=True), **KW)
    e.generate(_prompts(arch, n=2), max_new=3)
    assert e.cache.peak_blocks > 0 and e.obs.trace.events
    e.reset_stats()
    assert e.stats["decode_tokens"] == 0
    assert e.obs.trace.events == [], "reset clears the trace buffer"
    assert all(v == 0 for v in e.cache.counters().values()), \
        "one reset path covers every cache counter"
    assert e.obs.metrics.histogram("request_latency_s").count == 0


def test_replica_merge_equals_fleet():
    """replica ⊕ replica == fleet: merging two engines' snapshots gives the
    totals a single fleet-wide registry would hold."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(1)
    e1 = PagedServeEngine(arch, params, **KW)
    e2 = PagedServeEngine(arch, params, **KW)
    e1.generate(_prompts(arch, n=2, rng=rng), max_new=3)
    e2.generate(_prompts(arch, n=3, rng=rng), max_new=3)
    s1, s2 = e1.metrics_snapshot(), e2.metrics_snapshot()
    fleet = merge_snapshots(s1, s2)
    assert fleet["requests_completed"]["value"] == 5
    assert fleet["serve_decode_tokens"]["value"] == (
        s1["serve_decode_tokens"]["value"] + s2["serve_decode_tokens"]["value"])
    assert fleet["kv_peak_blocks"]["value"] == max(
        s1["kv_peak_blocks"]["value"], s2["kv_peak_blocks"]["value"])
    lat = fleet["request_latency_s"]["values"]
    assert sorted(lat) == sorted(s1["request_latency_s"]["values"]
                                 + s2["request_latency_s"]["values"])
    assert percentile(lat, 99) == max(lat)


# -- names on device work ------------------------------------------------------

_f32, _bf16, _i8, _i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
_B, _KV, _G, _DH, _BS, _NB, _MB, _H, _R, _P = 2, 2, 2, 128, 16, 9, 4, 8, 128, 64
# kernel name -> (call, operand shapes, lowers for TPU).  The RWKV-6 scan's
# transposed-operand dot does not lower for TPU in this JAX; its name is read
# from the traced program only.
_KERNELS = {
    "int_matmul": (lambda x, w, s: ops.int_matmul(x, w, scale=s, interpret=False),
                   [((8, 256), _i8), ((256, 256), _i8), ((256,), _f32)], True),
    "a2q_quantize": (lambda v, t, d: ops.a2q_quantize(
        v, t, d, weight_bits=8, acc_bits=16, input_bits=8, input_signed=True, interpret=False),
        [((256, 256), _f32), ((256,), _f32), ((256,), _f32)], True),
    "flash_attention": (lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
                        [((1, 2, 128, 128), _bf16)] * 3, True),
    "paged_attention": (lambda q, kp, vp, bt, ln: ops.paged_attention(
        q, kp, vp, bt, ln, interpret=False),
        [((_B, _KV * _G, _DH), _bf16), ((_NB, _BS, _KV, _DH), _bf16),
         ((_NB, _BS, _KV, _DH), _bf16), ((_B, _MB), _i32), ((_B,), _i32)], True),
    "paged_mla_attention": (lambda ql, qp, c, k, bt, ln: ops.paged_mla_attention(
        ql, qp, c, k, bt, ln, scale=0.1, interpret=False),
        [((_B, _H, _R), _f32), ((_B, _H, _P), _f32), ((_NB, _BS, _R), _bf16),
         ((_NB, _BS, _P), _bf16), ((_B, _MB), _i32), ((_B,), _i32)], True),
    "rwkv6_scan": (lambda r, k, v, w, u: ops.rwkv6_scan(r, k, v, w, u, interpret=False),
                   [((1, 1, 64, 64), _f32)] * 4 + [((1, 64), _f32)], False),
}


def _pallas_names(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params.get("name")
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_names(sub)


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_every_pallas_call_is_named(name):
    kernels = pathlib.Path(ops.__file__).parent
    calls = sum(f.read_text().count("pl.pallas_call(") for f in kernels.glob("*.py"))
    assert calls == len(_KERNELS), "a pallas_call without a case here"
    fn, shapes, tpu = _KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    assert list(_pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)) == [name]
    if tpu:
        text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert re.findall(r'kernel_name = "([^"]*)"', text) == [name]


@pytest.fixture(scope="module")
def scoped_programs():
    """The lowered text, with locations, of a jitted A2Q train step and of the
    decode megastep of reduced models."""
    from repro.models.steps import build_train_step
    from repro.optim.optimizers import adamw

    arch = reduced(get_arch("smollm-135m"))
    params = _params(arch)
    opt = adamw()
    state = {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    toks = jnp.zeros((2, 16), jnp.int32)
    train = jax.jit(build_train_step(arch, opt, Runtime())).lower(
        state, {"tokens": toks, "targets": toks}).as_text(debug_info=True)
    sarch = reduced(get_arch("yi-6b"))
    e = PagedServeEngine(sarch, _params(sarch), decode_steps=2, **KW)
    b = e.batch
    mega = e._megadecode.lower(
        e.params, jnp.zeros((b,), jnp.int32), e.cache.pools, e.cache.bt(),
        jnp.zeros((b,), jnp.int32), jnp.ones((b,), bool), jnp.full((b,), 4, jnp.int32),
        jnp.full((b,), -1, jnp.int32), jax.random.PRNGKey(0)).as_text(debug_info=True)
    return {"train": train, "megastep": mega}


@pytest.mark.parametrize("program,scope", [
    ("train", "attention"), ("train", "mlp"), ("train", "a2q_weight_quant"),
    ("train", "act_quant"), ("train", "embed"), ("train", "head"), ("train", "loss"),
    ("train", "optimizer"),
    ("megastep", "attention"), ("megastep", "kv_write"), ("megastep", "mlp"),
    ("megastep", "sample"),
])
def test_named_scopes_in_the_lowered_program(scoped_programs, program, scope):
    # a scope is a path component of an operation's location; transforms
    # wrap it (``transpose(jvp(attention))``) in the backward pass
    pat = re.compile(r'loc\("(?:[^"]*/)?(?:\w+\()*' + scope + r'\)*/[^"]*"')
    assert pat.search(scoped_programs[program]), (program, scope)
