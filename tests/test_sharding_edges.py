"""Edge cases for repro.dist beyond the seed contract tests: degenerate
meshes, fused-QKV unit counts, boxed-tree spec derivation, and the
compressed-collective quantization contracts on a single device (fast,
in-process) — including the per-column (A2Q+-style) scale mode, the static
overflow guard, and the grad-compress residual state layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

from repro.configs import get_arch, reduced
from repro.dist.collectives import (
    GradCompressConfig,
    compressed_allreduce_tree,
    compressed_psum,
    compressed_psum_tree,
    owner_dim,
    quantize_shared_scale,
    resolve_grad_compress,
    server_shape,
)
from repro.dist.sharding import ShardingRules, cache_specs, make_mesh, param_specs, resolve_pspec
from repro.nn.module import box


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_one_axis_mesh_data_only():
    """No 'model' axis: TP-ish dims replicate, FSDP/batch still shard."""
    mesh = _FakeMesh({"data": 8})
    arch = get_arch("smollm-135m")
    rules = ShardingRules.default(mesh, arch)
    assert rules.rules["batch"] == ("data",)
    assert resolve_pspec(("embed", "heads"), (576, 576), mesh, rules) == P("data", None)
    # mlp wants 'model' which doesn't exist -> replicated
    assert resolve_pspec(("embed", "mlp"), (576, 1536), mesh, rules) == P("data", None)


def test_mesh_size_one_everything_replicated():
    """Size-1 axes are skipped: single-device specs are fully replicated."""
    mesh = _FakeMesh({"data": 1, "model": 1})
    arch = get_arch("smollm-135m")
    rules = ShardingRules.default(mesh, arch)
    assert resolve_pspec(("embed", "mlp"), (576, 1536), mesh, rules) == P(None, None)
    assert resolve_pspec(("batch", None, None), (8, 64, 1), mesh, rules) == P(None, None, None)


def test_fused_qkv_heads_divide_kv_heads_do_not():
    """yi-6b on a (2, 16) mesh: 32 heads shard 16-way, 4 kv_heads cannot —
    even though the fused kv dim 4*128=512 itself divides 16."""
    mesh = _FakeMesh({"data": 2, "model": 16})
    arch = get_arch("yi-6b")
    rules = ShardingRules.default(mesh, arch)
    assert rules.unit_counts["heads"] == 32 and rules.unit_counts["kv_heads"] == 4
    assert resolve_pspec(("embed", "heads"), (4096, 4096), mesh, rules) == P("data", "model")
    assert (512 % 16) == 0  # raw-dim divisibility would wrongly shard...
    assert resolve_pspec(("embed", "kv_heads"), (4096, 512), mesh, rules) == P("data", None)


def test_multi_axis_rule_prefers_largest_valid_subset():
    """batch rule ('pod', 'data') with batch=8 on {pod: 2, data: 8}: the full
    16-way extent doesn't divide, and 'data' alone (8-way) beats 'pod' (2-way)."""
    mesh = _FakeMesh({"pod": 2, "data": 8})
    rules = ShardingRules.default(mesh, None)
    assert rules.rules["batch"] == ("pod", "data")
    assert resolve_pspec(("batch", None), (8, 4), mesh, rules) == P("data", None)
    # divisible by the full extent -> both axes, earlier-first
    assert resolve_pspec(("batch", None), (16, 4), mesh, rules) == P(("pod", "data"), None)


def test_param_specs_on_boxed_tree():
    mesh = _FakeMesh({"data": 2, "model": 4})
    arch = get_arch("yi-6b")
    rules = ShardingRules.default(mesh, arch)
    tree = {
        "wq": box(jnp.zeros((4096, 4096)), ("embed", "heads")),
        "norm": box(jnp.zeros((4096,)), (None,)),
        "plain": jnp.zeros((3, 3)),  # non-boxed leaves replicate
    }
    specs = param_specs(tree, mesh, rules)
    assert specs["wq"] == P("data", "model")
    assert specs["norm"] == P(None)
    assert specs["plain"] == P(None, None)


def test_compressed_psum_single_device_contract():
    """On a 1-device mesh the psum is an identity: the 'total' is the
    dequantized payload, the residual is exactly what quantization dropped,
    and total + err reconstructs the payload bit-for-bit."""
    mesh = make_mesh((1,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    err0 = jnp.zeros_like(x)

    f = jax.shard_map(
        lambda xs, es: compressed_psum(xs, "data", es, bits=8),
        mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    total, err = f(x, err0)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert float(jnp.abs(total - x).max()) <= scale / 2 + 1e-7
    np.testing.assert_allclose(np.asarray(total + err), np.asarray(x), rtol=0, atol=1e-7)
    assert float(jnp.abs(err).max()) > 0  # normal data never quantizes exactly


def test_compressed_psum_tree_structure():
    mesh = make_mesh((1,), ("data",))
    tree = {"a": jnp.ones((2, 4)), "b": {"c": jnp.full((3,), 0.3)}}
    errs = jax.tree.map(jnp.zeros_like, tree)

    f = jax.shard_map(
        lambda t, e: compressed_psum_tree(t, "data", e, bits=8),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    total, new_errs = f(tree, errs)
    assert jax.tree_util.tree_structure(total) == jax.tree_util.tree_structure(tree)
    assert jax.tree_util.tree_structure(new_errs) == jax.tree_util.tree_structure(tree)
    assert float(jnp.abs(total["a"] - 1.0).max()) < 1e-2


def test_compressed_psum_rejects_bad_bits():
    with pytest.raises(ValueError):
        compressed_psum(jnp.ones((2,)), "data", jnp.zeros((2,)), bits=1)


def test_compressed_psum_rejects_bad_scale_axis():
    with pytest.raises(ValueError):
        compressed_psum(jnp.ones((2,)), "data", jnp.zeros((2,)), scale_axis="row")


def test_compressed_psum_requires_bound_axis():
    """Outside shard_map the axis has no static size -> clear error, not a
    silently-skipped guard."""
    with pytest.raises(ValueError, match="static size"):
        compressed_psum(jnp.ones((2,)), "data", jnp.zeros((2,)))


def test_overflow_guard_raises_at_trace_time():
    """The Eq.-12-style static guard must actually fire: 2**17 shards at
    int16 overflows the int32 accumulator.  AbstractMesh traces the
    shard_map without devices, so the guard is exercised at trace time."""
    from jax.sharding import AbstractMesh

    n = 1 << 17
    am = AbstractMesh((n,), ("data",))
    x = jax.ShapeDtypeStruct((n, 4), jnp.float32)

    def f(xs, es):
        return compressed_psum(xs, "data", es, bits=16)

    g = jax.shard_map(f, mesh=am, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
    with pytest.raises(ValueError, match="overflow"):
        jax.eval_shape(g, x, x)
    # int8 at the same width is fine: 2**17 * 127 << 2**31
    g8 = jax.shard_map(lambda xs, es: compressed_psum(xs, "data", es, bits=8),
                       mesh=am, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_vma=False)
    jax.eval_shape(g8, x, x)


@settings(max_examples=12, deadline=None)
@given(
    bits=st.integers(2, 16),
    rows=st.integers(1, 5),
    cols=st.integers(1, 6),
)
def test_quantize_wire_format(bits, rows, cols):
    """Wire payload contract: int8 for bits<=8 / int16 above, one scale
    scalar for tensor mode, one fp32 scale per output column for column
    mode (rank>=2)."""
    mesh = make_mesh((1,), ("data",))
    y = jax.random.normal(jax.random.PRNGKey(bits), (rows, cols), jnp.float32)

    def f(ys):
        qt, st_ = quantize_shared_scale(ys, "data", bits, "tensor")
        qc, sc = quantize_shared_scale(ys, "data", bits, "column")
        return qt, st_, qc, sc

    qt, st_, qc, sc = jax.shard_map(
        f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(y)
    want = jnp.int8 if bits <= 8 else jnp.int16
    assert qt.dtype == want and qc.dtype == want
    assert st_.shape == () and st_.dtype == jnp.float32
    assert sc.shape == (1, cols) and sc.dtype == jnp.float32
    qmax = 2 ** (bits - 1) - 1
    assert int(jnp.abs(qt).max()) <= qmax and int(jnp.abs(qc).max()) <= qmax


@settings(max_examples=10, deadline=None)
@given(
    cols=st.integers(2, 8),
    spread=st.floats(1.5, 100.0),
)
def test_per_column_scale_exact_on_column_constant(cols, spread):
    """A payload whose every column is constant is represented exactly by
    per-column scales (each column quantizes to +-qmax), while a shared
    tensor scale loses the small columns — the A2Q+ granularity argument."""
    mesh = make_mesh((1,), ("data",))
    vals = jnp.linspace(1.0, spread, cols)
    x = jnp.tile(vals[None, :], (4, 1)).astype(jnp.float32)
    err0 = jnp.zeros_like(x)

    def run(scale_axis):
        f = jax.shard_map(
            lambda xs, es: compressed_psum(xs, "data", es, bits=8, scale_axis=scale_axis),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False,
        )
        total, err = f(x, err0)
        return float(jnp.abs(total - x).max())

    err_col = run("column")
    err_tensor = run("tensor")
    assert err_col <= 1e-5 * spread, err_col
    # the shared scale cannot represent column 0 (magnitude 1) exactly when
    # the largest column sets the scale
    if spread > 3:
        assert err_tensor > err_col


def test_compressed_psum_column_tree_mixed_ranks():
    """Tree mode with per-column scales: rank>=2 leaves get column scales,
    rank-1 leaves fall back to the tensor scale — both still reconstruct
    payload = total + err on one device."""
    mesh = make_mesh((1,), ("data",))
    tree = {
        "w": jnp.asarray([[0.5, 40.0], [0.5, 40.0]], jnp.float32),
        "b": jnp.asarray([0.1, -0.2, 0.3], jnp.float32),
    }
    errs = jax.tree.map(jnp.zeros_like, tree)
    f = jax.shard_map(
        lambda t, e: compressed_psum_tree(t, "data", e, bits=8, scale_axis="column"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False,
    )
    total, err = f(tree, errs)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(total[k] + err[k]), np.asarray(tree[k]), rtol=0, atol=1e-6
        )
    # column-constant leaf "w" columns are exact under per-column scales
    assert float(jnp.abs(total["w"] - tree["w"]).max()) < 1e-4


def test_compressed_allreduce_tree_single_device_contract():
    """The global-view (GSPMD) transport on one device: total ~= payload,
    total + local residual reconstructs it, structure preserved."""
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 6), jnp.float32),
            "s": jnp.float32(0.7)}
    stacked = jax.tree.map(lambda t: t[None], tree)
    err = {
        "local": jax.tree.map(jnp.zeros_like, stacked),
        "server": jax.tree.map(lambda t: jnp.zeros(server_shape(t.shape, 1), jnp.float32), tree),
    }

    def f(g, e):
        return compressed_allreduce_tree(g, e, mesh=mesh, axis="data", bits=8)

    total, new_err = jax.jit(f)(stacked, err)
    assert jax.tree_util.tree_structure(total) == jax.tree_util.tree_structure(tree)
    scale = float(jnp.abs(tree["w"]).max()) / 127.0
    assert float(jnp.abs(total["w"] - tree["w"]).max()) <= scale / 2 + 1e-7
    recon = total["w"] + new_err["local"]["w"][0]
    np.testing.assert_allclose(np.asarray(recon), np.asarray(tree["w"]), rtol=0, atol=1e-6)
    assert abs(float(total["s"]) - 0.7) <= float(jnp.abs(tree['s'])) / 127.0 + 1e-7


def test_owner_dim_prefers_axis_then_free_dim():
    assert owner_dim(P("model", "data"), 2, "data") == 1  # FSDP dim wins
    assert owner_dim(P(None, "data", "model"), 3, "data") == 1
    assert owner_dim(P("model", None), 2, "data") == 1  # free dim
    assert owner_dim(P("model", "model2"), 2, "data") == 0  # fallback
    assert owner_dim(None, 3, "data") == 0
    assert server_shape((30, 576), 16, 0) == (32, 576)
    assert server_shape((), 4) == (4,)


def test_owner_dim_sees_multi_axis_tuple_fsdp_dims():
    """Regression (ROADMAP nit): an FSDP dim spelled inside a multi-axis
    PartitionSpec tuple — P(("pod", "data"), ...) on a multi-pod mesh — must
    win ownership like the bare spelling does; missing it pushed ownership
    onto a free dim and cost an extra all-gather per leaf (wire only)."""
    assert owner_dim(P(("pod", "data"), "model"), 2, "data") == 0
    assert owner_dim(P("model", ("data", "model2")), 2, "data") == 1
    assert owner_dim(P(None, ("pod", "data")), 2, "data") == 1
    # the axis singleton-tuple spelling keeps working
    assert owner_dim(P(("data",), "model"), 2, "data") == 0
    # tuples NOT carrying the axis still lose to a later bare/free dim
    assert owner_dim(P(("pod", "model"), "data"), 2, "data") == 1
    assert owner_dim(P(("pod", "model"), None), 2, "data") == 1


def test_compressed_allreduce_tuple_pspec_numerics():
    """The multi-axis-tuple owner dim must not change the math: global-view
    compressed sum with P(("pod", "data"), ...) param layout equals the sum
    of shard contributions within wire tolerance."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.dist.collectives import compressed_allreduce, server_shape as ss

    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(devs, ("pod", "data", "model"))
    n = int(mesh.shape["data"])
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32)
    pspec = P(("pod", "data"), "model")
    od = owner_dim(pspec, 2, "data")
    assert od == 0
    with mesh:
        total, new_local, new_server = compressed_allreduce(
            g, jnp.zeros_like(g), jnp.zeros(ss((6, 4), n, od), jnp.float32),
            mesh=mesh, axis="data", pspec=pspec,
        )
    want = np.asarray(g).sum(0)
    scale = np.abs(np.asarray(g)).max() / 127.0
    assert np.abs(np.asarray(total) - want).max() <= n * scale + 1e-6
    assert new_server.shape == ss((6, 4), n, od)


def test_resolve_grad_compress_axis_selection():
    cfg = GradCompressConfig(bits=8)
    single = _FakeMesh({"data": 8, "model": 2})
    multi = _FakeMesh({"pod": 2, "data": 8, "model": 2})
    tiny = _FakeMesh({"data": 1})
    assert resolve_grad_compress(cfg, single).axis == "data"
    assert resolve_grad_compress(cfg, multi).axis == "pod"  # DCN wire first
    assert resolve_grad_compress(GradCompressConfig(axis="data"), multi).axis == "data"
    assert resolve_grad_compress(cfg, tiny) is None
    assert resolve_grad_compress(cfg, None) is None
    assert resolve_grad_compress(None, single) is None


def test_cache_specs_kv_heads_sharding():
    """K/V cache leaves shard their head dim over `model` when the kv_heads
    unit count divides it — and fall back to replicated when it does not
    (smollm's 3 kv-heads vs a 16-way axis)."""
    from repro.models.lm import init_cache

    # yi-6b: kv_heads=4 divides model=4
    mesh = _FakeMesh({"data": 2, "model": 4})
    arch = get_arch("yi-6b")
    rules = ShardingRules.default(mesh, arch)
    cache = jax.eval_shape(lambda: init_cache(arch, 8, 64))
    specs = cache_specs(cache, mesh, rules)
    k_spec = specs["0"]["attn"]["k"]
    assert k_spec == P(None, "data", None, "model", None)
    assert specs["0"]["attn"]["kpos"] == P(None, "data", None)

    # smollm: 3 kv heads never split over 16
    mesh16 = _FakeMesh({"data": 2, "model": 16})
    sm = get_arch("smollm-135m")
    rules16 = ShardingRules.default(mesh16, sm)
    cache_sm = jax.eval_shape(lambda: init_cache(sm, 8, 64))
    k_sm = cache_specs(cache_sm, mesh16, rules16)["0"]["attn"]["k"]
    assert k_sm == P(None, "data", None, None, None)

    # rwkv6: SSM state (layers, batch, heads, hd, hd) shards heads (64 % 16 == 0)
    rw = get_arch("rwkv6-7b")
    rules_rw = ShardingRules.default(mesh16, rw)
    cache_rw = jax.eval_shape(lambda: init_cache(rw, 16, 64))
    s_spec = cache_specs(cache_rw, mesh16, rules_rw)["0"]["tm"]["S"]
    assert s_spec[2] == "model"


def test_cache_specs_paged_layout():
    """Paged pools: block axis local (any row may own any block), head dim
    keeps the TP sharding of the projections that fill it; MLA latent pools
    replicate; the block table rides with the batch axes."""
    from repro.serve.paged_cache import init_paged_stack_cache

    mesh = _FakeMesh({"data": 2, "model": 4})
    arch = get_arch("yi-6b")
    rules = ShardingRules.default(mesh, arch)
    cache = jax.eval_shape(
        lambda: {
            "0": init_paged_stack_cache(arch, arch.stacks[0], 8, 32, 16, 64, jnp.bfloat16),
            "_paged": {"bt": jnp.zeros((8, 4), jnp.int32)},
        }
    )
    specs = cache_specs(cache, mesh, rules)
    # (layers, NB, bs, kv_heads, head_dim): only the head dim shards
    assert specs["0"]["attn"]["kp"] == P(None, None, None, "model", None)
    assert specs["0"]["attn"]["vp"] == P(None, None, None, "model", None)
    assert specs["_paged"]["bt"] == P("data", None)

    ds = get_arch("deepseek-v3-671b")
    rules_ds = ShardingRules.default(mesh, ds)
    mla = next(s for s in ds.stacks if s.attn is not None and s.attn.kind == "mla")
    cache_ds = jax.eval_shape(
        lambda: {"0": init_paged_stack_cache(ds, mla, 8, 32, 16, 64, jnp.bfloat16)}
    )
    specs_ds = cache_specs(cache_ds, mesh, rules_ds)
    assert specs_ds["0"]["attn"]["ckvp"] == P(None, None, None, None)
    assert specs_ds["0"]["attn"]["kpep"] == P(None, None, None, None)


def test_cache_specs_int8_pools_and_scale_leaves():
    """int8 code pools keep the paged layout specs (dtype is irrelevant to
    sharding); the per-slot scale pools shard their trailing kv_heads dim
    over `model` like the codes they scale (GQA) and replicate for MLA —
    with the usual unit-count fallback."""
    from repro.serve.paged_cache import init_paged_stack_cache

    mesh = _FakeMesh({"data": 2, "model": 4})
    arch = get_arch("yi-6b")
    rules = ShardingRules.default(mesh, arch)
    cache = jax.eval_shape(
        lambda: {"0": init_paged_stack_cache(
            arch, arch.stacks[0], 8, 32, 16, 64, jnp.bfloat16, kv_quant=True
        )}
    )
    specs = cache_specs(cache, mesh, rules)["0"]["attn"]
    assert cache["0"]["attn"]["kp"].dtype == jnp.int8
    assert specs["kp"] == P(None, None, None, "model", None)
    assert specs["kps"] == P(None, None, None, "model")
    assert specs["vps"] == P(None, None, None, "model")

    # smollm's 3 kv-heads: codes AND scales both fall back to replicated
    mesh16 = _FakeMesh({"data": 2, "model": 16})
    sm = get_arch("smollm-135m")
    rules16 = ShardingRules.default(mesh16, sm)
    cache_sm = jax.eval_shape(
        lambda: {"0": init_paged_stack_cache(
            sm, sm.stacks[0], 8, 32, 16, 64, jnp.bfloat16, kv_quant=True
        )}
    )
    specs_sm = cache_specs(cache_sm, mesh16, rules16)["0"]["attn"]
    assert specs_sm["kp"] == P(None, None, None, None, None)
    assert specs_sm["kps"] == P(None, None, None, None)

    # MLA latent scale pools carry nothing shardable
    ds = get_arch("deepseek-v3-671b")
    rules_ds = ShardingRules.default(mesh, ds)
    mla = next(s for s in ds.stacks if s.attn is not None and s.attn.kind == "mla")
    cache_ds = jax.eval_shape(
        lambda: {"0": init_paged_stack_cache(ds, mla, 8, 32, 16, 64, jnp.bfloat16, kv_quant=True)}
    )
    specs_ds = cache_specs(cache_ds, mesh, rules_ds)["0"]["attn"]
    assert cache_ds["0"]["attn"]["ckvp"].dtype == jnp.int8
    assert specs_ds["ckvs"] == P(None, None, None)
    assert specs_ds["kpes"] == P(None, None, None)


def test_make_state_specs_and_init_grad_err_layout():
    """grad_err residual pair: local = P(axis, param-spec minus axis);
    server = param layout with the ownership dim on the axis; shapes from
    init_grad_err line up leaf-for-leaf."""
    from repro.models import init_lm
    from repro.nn.module import unbox
    from repro.optim.optimizers import adamw
    from repro.train.state import init_grad_err, make_state_specs

    arch = reduced(get_arch("smollm-135m"))
    mesh = _FakeMesh({"data": 2, "model": 4})
    rules = ShardingRules.default(mesh, arch)
    boxed = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), arch))
    params = unbox(boxed)
    gc = GradCompressConfig(bits=8, axis="data")
    specs = make_state_specs(boxed, adamw(), mesh, rules, grad_compress=gc)
    assert set(specs) == {"params", "opt_state", "step", "grad_err"}
    pspecs = param_specs(boxed, mesh, rules)
    err = jax.eval_shape(lambda: init_grad_err(params, 2, pspecs=pspecs, axis="data"))

    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_local = dict(jax.tree_util.tree_flatten_with_path(err["local"])[0])
    flat_server = dict(jax.tree_util.tree_flatten_with_path(err["server"])[0])
    flat_ls = dict(
        jax.tree_util.tree_flatten_with_path(
            specs["grad_err"]["local"], is_leaf=lambda x: isinstance(x, P)
        )[0]
    )
    flat_ss = dict(
        jax.tree_util.tree_flatten_with_path(
            specs["grad_err"]["server"], is_leaf=lambda x: isinstance(x, P)
        )[0]
    )
    for path, p in flat_p:
        local, server = flat_local[path], flat_server[path]
        ls, ss = flat_ls[path], flat_ss[path]
        assert local.shape == (2,) + tuple(p.shape)
        assert len(ls) == local.ndim and ls[0] == "data"
        assert "data" not in tuple(ls)[1:]  # no axis reuse
        assert len(ss) <= max(server.ndim, 1)
        assert local.dtype == server.dtype == jnp.float32

    # grad_compress with an unresolved axis is a caller bug
    with pytest.raises(ValueError):
        make_state_specs(boxed, adamw(), mesh, rules, grad_compress=GradCompressConfig())
