"""deploy_params / deploy_boxed: int8 deployment tree transforms.

Covers the satellite gaps: passthrough of ``aq``/``b`` leaves, vmapped
leading dims (scan-stacked layers and experts), shape-level twin agreement,
and int8-vs-float logits parity on a reduced arch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models.lm import apply_lm, init_lm
from repro.nn.module import Boxed, unbox
from repro.serve.engine import deploy_boxed, deploy_params, init_deployed_lm

KEY = jax.random.PRNGKey(0)


def _walk_deployed(tree):
    """Deployed {q8, s8} nodes keyed by tree path (order-independent)."""
    found = {}

    def walk(node, path=()):
        if isinstance(node, dict):
            if "q8" in node:
                found[path] = node
            else:
                for k, v in node.items():
                    walk(v, path + (k,))

    walk(tree)
    return found


def test_deploy_passes_through_aq_and_bias():
    """Activation-quantizer (aq) and bias (b) leaves survive deployment
    untouched — they are runtime state, not weight storage."""
    import dataclasses

    # force biases on so the b-passthrough is actually exercised
    arch = dataclasses.replace(reduced(get_arch("yi-6b")), use_bias=True)
    params = unbox(init_lm(KEY, arch))
    deployed = deploy_params(params, arch.quant)

    def collect(tree, key):
        out = []
        jax.tree_util.tree_map_with_path(
            lambda p, l: out.append((p, l)) if any(
                getattr(k, "key", None) == key for k in p
            ) else None,
            tree,
        )
        return out

    for key in ("aq", "b"):
        before = collect(params, key)
        after = collect(deployed, key)
        assert len(before) == len(after) and len(after) > 0, key
        for (_, x), (_, y) in zip(before, after):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_deploy_vmaps_stacked_layers_and_experts():
    """Scan-stacked linears (layers dim) and MoE expert stacks (experts dim)
    deploy via vmap over the leading dims: q8/s8 keep those dims."""
    arch = reduced(get_arch("deepseek-v3-671b"))  # scan layers + experts
    params = unbox(init_lm(KEY, arch))
    deployed = deploy_params(params, arch.quant)
    nodes = list(_walk_deployed(deployed).values())
    assert nodes
    ranks = {n["q8"].ndim for n in nodes}
    assert max(ranks) >= 3, "no stacked (vmapped) deployments found"
    for n in nodes:
        assert n["q8"].dtype == jnp.int8
        # s8 scales: one per output channel, aligned with q8's trailing dim
        assert n["s8"].shape[-1] == n["q8"].shape[-1]
        assert n["s8"].shape[:-1] == n["q8"].shape[:-2]


def test_deploy_boxed_mirrors_deploy_params_shapes():
    """The dry-run's shape-level twin must produce exactly the shapes/dtypes
    the materializing transform produces, with logical axes preserved."""
    arch = reduced(get_arch("yi-6b"))
    boxed = init_lm(KEY, arch)
    deployed = deploy_params(unbox(boxed), arch.quant)
    boxed_deployed = deploy_boxed(boxed, arch.quant)

    real = _walk_deployed(deployed)
    shaped = _walk_deployed(boxed_deployed)
    assert set(real) == set(shaped) and real
    for path, r in real.items():
        s = shaped[path]
        for k in ("q8", "s8"):
            leaf = s[k]
            assert isinstance(leaf, Boxed)
            assert tuple(leaf.value.shape) == tuple(r[k].shape), (path, k)
            assert leaf.value.dtype == r[k].dtype
            assert len(leaf.axes) == r[k].ndim


@pytest.mark.parametrize("name", ["yi-6b", "rwkv6-7b", "deepseek-v3-671b"])
def test_init_deployed_lm_matches_deploy_of_init(name):
    """The layer-at-a-time deployed init is the deployed full init: same
    tree, identical int8 codes, float leaves (scales, norms, quantizer
    state) equal up to jit-vs-eager rounding."""
    arch = reduced(get_arch(name))
    want = deploy_params(unbox(init_lm(KEY, arch)), arch.quant)
    got = init_deployed_lm(KEY, arch)
    flat_w, tree_w = jax.tree_util.tree_flatten_with_path(want)
    flat_g, tree_g = jax.tree_util.tree_flatten_with_path(got)
    assert tree_w == tree_g
    for (path, w), (_, g) in zip(flat_w, flat_g):
        assert w.shape == g.shape and w.dtype == g.dtype, path
        if w.dtype == jnp.int8:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=str(path))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-9,
                                       err_msg=str(path))


@pytest.mark.parametrize("name", ["yi-6b", "deepseek-v3-671b"])
def test_int_forward_logits_parity_close(name):
    """The fused W8A8 path computes the same quantized algebra as dequant +
    fp32 dot exactly in integers, so logits agree to ~ulp on the reduced
    archs (GQA and MLA) and greedy argmax is preserved."""
    from repro.models.lm import Runtime

    arch = reduced(get_arch(name))
    deployed = deploy_params(unbox(init_lm(KEY, arch)), arch.quant)
    toks = jnp.asarray([[5, 1, 3, 2, 7, 6]], jnp.int32)
    l_deq, _, _ = apply_lm(deployed, arch, tokens=toks)
    l_int, _, _ = apply_lm(deployed, arch, tokens=toks, rt=Runtime(int_forward=True))
    np.testing.assert_allclose(np.asarray(l_deq), np.asarray(l_int), atol=1e-5)
    assert (np.argmax(np.asarray(l_deq), -1) == np.argmax(np.asarray(l_int), -1)).all()


def test_int_forward_exact_when_scales_pow2_and_acts_integral():
    """Int8-exactness witness: with pow2 activation AND weight scales and
    integer-valued inputs, every fp32 product/sum on the dequant path is
    exact, so the dequant dot and the W8A8 kernel are the same arithmetic —
    bitwise-equal outputs (the general case is ~ulp-close: non-pow2 weight
    scales round once per product on the dequant side)."""
    from repro.configs.base import QuantConfig
    from repro.nn.linear import apply_linear

    cfg = QuantConfig(mode="a2q", weight_bits=8, act_bits=8, acc_bits=16)
    rng = np.random.default_rng(0)
    dep = {
        "q8": jnp.asarray(rng.integers(-16, 16, (32, 48)), jnp.int8),
        "s8": jnp.exp2(jnp.asarray(rng.integers(-6, -2, (48,)), jnp.float32)),
        "aq": {"log2_scale": jnp.zeros(())},  # scale = 2**0: acts stay integral
    }
    x = jnp.asarray(rng.integers(-20, 20, (4, 32)), jnp.float32)
    y_deq = apply_linear(dep, x, cfg, compute_dtype=jnp.float32)
    y_int = apply_linear(dep, x, cfg, compute_dtype=jnp.float32, int_forward=True)
    np.testing.assert_array_equal(np.asarray(y_deq), np.asarray(y_int))


def test_int_forward_rwkv6_unsigned_channelmix_fused():
    """rwkv6's channel-mix ``wv`` consumes unsigned 8-bit acts (post-relu²,
    codes up to 255 — past the int8 operand).  It now rides the fused W8A8
    path via signed symmetrization (codes travel as ``q - 128``, the kernel
    adds ``128 * colsum(w)`` back at flush — exact in int32): logits stay
    ~ulp-close AND the chain report shows zero fallback call sites."""
    from repro.models.lm import Runtime

    arch = reduced(get_arch("rwkv6-7b"))
    deployed = deploy_params(unbox(init_lm(KEY, arch)), arch.quant)
    toks = jnp.asarray([[5, 1, 3, 2, 7, 6, 9, 8]], jnp.int32)  # T % ssm chunk == 0
    l_deq, _, _ = apply_lm(deployed, arch, tokens=toks)
    rt = Runtime(int_forward=True)
    l_int, _, _ = apply_lm(deployed, arch, tokens=toks, rt=rt)
    np.testing.assert_allclose(np.asarray(l_deq), np.asarray(l_int), atol=1e-5)
    assert rt.chain_report["fallback"] == [], rt.chain_report
    assert "cm.wv" in rt.chain_report["standalone"]  # fused, own act-quant dispatch


def test_int_forward_falls_back_off_the_int8_path():
    """Stacked (vmapped) q8 and non-deployed params must take the dequant
    path unchanged under int_forward — same output as int_forward=False."""
    from repro.configs.base import QuantConfig
    from repro.nn.linear import apply_linear, init_linear

    cfg = QuantConfig(mode="a2q", weight_bits=8, act_bits=8, acc_bits=16)
    p = unbox(init_linear(KEY, 16, 24, cfg))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, 16)), jnp.float32)
    y0 = apply_linear(p, x, cfg, compute_dtype=jnp.float32)
    y1 = apply_linear(p, x, cfg, compute_dtype=jnp.float32, int_forward=True)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


@pytest.mark.parametrize("name", ["smollm-135m", "h2o-danube-1.8b"])
def test_deployed_logits_close_to_float_reduced(name):
    """int8 deployment is the same math as training fake-quant: logits agree
    tightly under f32 compute on reduced archs (tie-embeddings + windowed)."""
    arch = reduced(get_arch(name))
    params = unbox(init_lm(KEY, arch))
    deployed = deploy_params(params, arch.quant)
    toks = jnp.asarray([[5, 1, 3, 2, 7, 6]], jnp.int32)
    l1, _, _ = apply_lm(params, arch, tokens=toks)
    l2, _, _ = apply_lm(deployed, arch, tokens=toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-3)
    assert np.argmax(np.asarray(l1)[0, -1]) == np.argmax(np.asarray(l2)[0, -1])
