"""Layer-level behaviour: attention decode/parallel consistency, SSM chunked
vs sequential, MoE dispatch vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttnConfig, MoEConfig, QuantConfig, SSMConfig
from repro.nn import attention as attn
from repro.nn import moe, ssm
from repro.nn.module import unbox

KEY = jax.random.PRNGKey(0)
QF = QuantConfig(mode="none")
QA = QuantConfig(mode="a2q", weight_bits=8, act_bits=8, acc_bits=20)


def _decode_replay(p, a, q, x, steps, max_seq=64, **kw):
    cache = attn.init_attn_cache(x.shape[0], a, max_seq=max_seq, dtype=jnp.float32)
    outs = []
    for t in range(steps):
        o, cache = attn.apply_attention(
            p, x[:, t : t + 1], a, q, jnp.full((x.shape[0], 1), t, jnp.int32), cache,
            compute_dtype=jnp.float32, **kw,
        )
        outs.append(o)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("qcfg", [QF, QA])
@pytest.mark.parametrize("window,chunk", [(None, None), (8, None), (None, 8)])
def test_gqa_decode_matches_parallel(qcfg, window, chunk):
    a = AttnConfig(heads=4, kv_heads=2, head_dim=16, window=window, chunk=chunk)
    p = unbox(attn.init_attention(KEY, 64, a, qcfg))
    x = jax.random.normal(KEY, (2, 20, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(20)[None], (2, 20))
    full, _ = attn.apply_attention(p, x, a, qcfg, pos, q_chunk=8, compute_dtype=jnp.float32)
    dec = _decode_replay(p, a, qcfg, x, 20)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-4)


_DISPATCH = {  # case -> (attention config, T, cached, platform says TPU, kernel runs)
    "cpu": (AttnConfig(heads=4, kv_heads=2, head_dim=16), 128, False, False, False),
    "kernel": (AttnConfig(heads=4, kv_heads=2, head_dim=16), 128, False, True, True),
    "window": (AttnConfig(heads=4, kv_heads=2, head_dim=16, window=32), 128, False, True, False),
    "chunk": (AttnConfig(heads=4, kv_heads=2, head_dim=16, chunk=32), 128, False, True, False),
    "mla": (AttnConfig(kind="mla", heads=4, head_dim=16, q_lora_rank=24, kv_lora_rank=16,
                       qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16), 128, False, True, False),
    "cached": (AttnConfig(heads=4, kv_heads=2, head_dim=16), 128, True, True, False),
    "length": (AttnConfig(heads=4, kv_heads=2, head_dim=16), 96, False, True, False),
    "bidirectional": (AttnConfig(heads=4, kv_heads=2, head_dim=16, causal=False), 128, False,
                      True, False),
}


@pytest.mark.parametrize("case", sorted(_DISPATCH))
def test_training_attention_dispatch(monkeypatch, case):
    """Only a causal, uncached GQA call whose length is a multiple of the
    kernel's block takes the causal kernel, and only on a TPU; every other
    call keeps ``_sdpa`` (here the kernel stands in as ``_sdpa`` itself)."""
    from repro.kernels import ops

    a, T, cached, tpu, runs = _DISPATCH[case]
    calls = []

    def kernel(q, k, v):
        calls.append(q.shape)
        pos = jnp.arange(q.shape[1], dtype=jnp.int32)[None]
        return attn._sdpa(q, k, v, pos, pos, causal=True, window=None, chunk=None, q_chunk=256)

    monkeypatch.setattr(ops, "causal_attention", kernel)
    monkeypatch.setattr(attn, "_on_tpu", lambda: tpu)
    # shapes only: the choice is made while tracing
    p = jax.eval_shape(lambda: unbox(attn.init_attention(KEY, 32, a, QF)))
    x = jax.ShapeDtypeStruct((1, T, 32), jnp.float32)
    cache = (jax.eval_shape(lambda: attn.init_attn_cache(1, a, max_seq=T, dtype=jnp.float32))
             if cached else None)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    out, _ = jax.eval_shape(
        lambda p, x, c: attn.apply_attention(p, x, a, QF, pos, c, compute_dtype=jnp.float32),
        p, x, cache)
    assert calls == ([(1, T, 4, 16)] if runs else [])
    assert out.shape == (1, T, 32)


def test_encoder_attention_is_bidirectional():
    a = AttnConfig(heads=2, kv_heads=2, head_dim=8, causal=False, rope_theta=None)
    p = unbox(attn.init_attention(KEY, 16, a, QF))
    x = jax.random.normal(KEY, (1, 10, 16), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(10)[None], (1, 10))
    out, _ = attn.apply_attention(p, x, a, QF, pos, compute_dtype=jnp.float32)
    # position 0 must see position 9: perturb the last token, check pos 0 moves
    x2 = x.at[:, -1].add(1.0)
    out2, _ = attn.apply_attention(p, x2, a, QF, pos, compute_dtype=jnp.float32)
    assert float(jnp.abs(out2[:, 0] - out[:, 0]).max()) > 1e-6


@pytest.mark.parametrize("absorb", [False, True])
def test_mla_decode_matches_parallel(absorb):
    a = AttnConfig(kind="mla", heads=4, head_dim=16, q_lora_rank=24, kv_lora_rank=16,
                   qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
    p = unbox(attn.init_attention(KEY, 32, a, QA))
    x = jax.random.normal(KEY, (2, 12, 32), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    full, _ = attn.apply_attention(p, x, a, QA, pos, q_chunk=8, compute_dtype=jnp.float32)
    dec = _decode_replay(p, a, QA, x, 12, max_seq=16, mla_absorb=absorb)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-3)


def test_ring_cache_evicts_beyond_window():
    """A 500k-context decode with window W holds exactly W slots."""
    a = AttnConfig(heads=2, kv_heads=2, head_dim=8, window=4)
    cache = attn.init_attn_cache(1, a, max_seq=1 << 19)
    assert cache["k"].shape[1] == 4  # ring, not 524288
    p = unbox(attn.init_attention(KEY, 16, a, QF))
    x = jax.random.normal(KEY, (1, 10, 16), jnp.float32)
    dec = _decode_replay(p, a, QF, x, 10)
    pos = jnp.broadcast_to(jnp.arange(10)[None], (1, 10))
    full, _ = attn.apply_attention(p, x, a, QF, pos, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-4)


# ---------------------------------------------------------------------------
# SSM mixers
# ---------------------------------------------------------------------------


def test_rwkv6_chunked_equals_sequential():
    rng = np.random.default_rng(0)
    B, H, T, Dk = 2, 3, 64, 8
    args = (
        jnp.asarray(rng.normal(size=(B, H, T, Dk)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, H, T, Dk)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, H, T, Dk)), jnp.float32),
        jnp.asarray(rng.uniform(0.2, 0.999, size=(B, H, T, Dk)), jnp.float32),
        jnp.asarray(rng.normal(size=(H, Dk)), jnp.float32),
        jnp.zeros((B, H, Dk, Dk), jnp.float32),
    )
    y1, s1 = ssm.rwkv6_sequential(*args)
    y2, s2 = ssm.rwkv6_chunked(*args, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)


def test_ssd_chunked_equals_sequential():
    rng = np.random.default_rng(1)
    B, H, T, Dh, N = 2, 2, 48, 8, 4
    args = (
        jnp.asarray(rng.normal(size=(B, H, T, Dh)), jnp.float32),
        jnp.asarray(rng.uniform(0.3, 0.999, size=(B, H, T)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, H, T, N)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, H, T, N)), jnp.float32),
        jnp.zeros((B, H, Dh, N), jnp.float32),
    )
    y1, s1 = ssm.ssd_sequential(*args)
    y2, s2 = ssm.ssd_chunked(*args, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)


@pytest.mark.parametrize("mixer", ["timemix", "mamba"])
def test_mixer_decode_matches_parallel(mixer):
    x = jax.random.normal(KEY, (2, 16, 32), jnp.float32)
    if mixer == "timemix":
        sc = SSMConfig(kind="rwkv6", head_dim=8, chunk=8, lora_rank=8)
        p = unbox(ssm.init_rwkv6_timemix(KEY, 32, sc, QA))
        full, _ = ssm.apply_rwkv6_timemix(p, x, sc, QA, compute_dtype=jnp.float32)
        st = {"S": jnp.zeros((2, 4, 8, 8), jnp.float32), "shift": jnp.zeros((2, 1, 32), jnp.float32)}
        step = lambda xt, st: ssm.apply_rwkv6_timemix(p, xt, sc, QA, st, compute_dtype=jnp.float32)
    else:
        sc = SSMConfig(kind="mamba", head_dim=8, state_dim=4, chunk=8)
        p = unbox(ssm.init_mamba_heads(KEY, 32, sc, QA))
        full, _ = ssm.apply_mamba_heads(p, x, sc, QA, compute_dtype=jnp.float32)
        st = {"S": jnp.zeros((2, 4, 8, 4), jnp.float32)}
        step = lambda xt, st: ssm.apply_mamba_heads(p, xt, sc, QA, st, compute_dtype=jnp.float32)
    outs = []
    for t in range(16):
        o, st = step(x[:, t : t + 1], st)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full), atol=1e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def _dense_moe_reference(params, x, cfg):
    """Loop-over-experts oracle with unlimited capacity, top-k routing."""
    B, T, d = x.shape
    x2 = np.asarray(x.reshape(B * T, d), np.float64)
    probs = np.asarray(jax.nn.softmax(x.reshape(B * T, d) @ params["router"], -1), np.float64)
    k = cfg.top_k
    top = np.argsort(-probs, axis=-1)[:, :k]
    out = np.zeros_like(x2)
    for tok in range(x2.shape[0]):
        ps = probs[tok, top[tok]]
        ps = ps / ps.sum()
        for e, pw in zip(top[tok], ps):
            w_in = np.asarray(params["w_in"]["w"][e], np.float64)
            w_gate = np.asarray(params["w_gate"]["w"][e], np.float64)
            w_out = np.asarray(params["w_out"]["w"][e], np.float64)
            h = x2[tok] @ w_in
            g = x2[tok] @ w_gate
            silu = g / (1 + np.exp(-g))
            out[tok] += pw * ((silu * h) @ w_out)
    return out.reshape(B, T, d)


def test_moe_matches_dense_reference_with_ample_capacity():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=16, capacity_factor=8.0)
    p = unbox(moe.init_moe(KEY, 8, cfg, QF))
    x = jax.random.normal(KEY, (2, 6, 8), jnp.float32)
    got = moe.apply_moe(p, x, cfg, QF, compute_dtype=jnp.float32)
    want = _dense_moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3)


def test_moe_capacity_drops_tokens_not_crashes():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=16, capacity_factor=0.25)
    p = unbox(moe.init_moe(KEY, 8, cfg, QF))
    x = jax.random.normal(KEY, (2, 16, 8), jnp.float32)
    got = moe.apply_moe(p, x, cfg, QF, compute_dtype=jnp.float32)
    assert not bool(jnp.isnan(got).any())


def test_moe_shared_expert_contributes():
    cfg = MoEConfig(n_experts=4, top_k=1, d_ff=16, n_shared=1, shared_d_ff=16)
    p = unbox(moe.init_moe(KEY, 8, cfg, QF))
    x = jax.random.normal(KEY, (1, 4, 8), jnp.float32)
    full = moe.apply_moe(p, x, cfg, QF, compute_dtype=jnp.float32)
    p2 = dict(p, shared_out={"w": jnp.zeros_like(p["shared_out"]["w"])})
    no_shared = moe.apply_moe(p2, x, cfg, QF, compute_dtype=jnp.float32)
    assert float(jnp.abs(full - no_shared).max()) > 1e-6
