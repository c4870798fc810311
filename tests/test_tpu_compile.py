"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode (how every other kernel test runs on the CPU) checks neither
the TPU block-tiling rule nor VMEM limits.  These tests hand the kernels to
the TPU compiler for a *described* v5e chip — nothing runs, no chip is
needed — at the widths the serve path uses: yi-6b's d_model 4096 / d_ff
11008 matmuls with the A2Q int16 spill, its GQA decode (32 query heads over
4 KV heads, head_dim 128) over fp / int8 / packed-int4 pools, and the MLA
latent decode at deepseek-v3's rank, and the training attention with its
backward at the training cells' shapes, on one chip and per chip's heads
under the four-chip tensor-parallel mesh.  Each compile must contain the
kernel (``tpu_custom_call``), i.e. no interpret-mode fallback slipped in.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and test workers import every file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("mode", ["fused", "requant", "prologue"])
@pytest.mark.parametrize("M,K,N", [(8, 4096, 11008), (128, 11008, 4096)])
def test_int_matmul_compiles(one_chip, mode, M, K, N):
    f32, i8 = jnp.float32, jnp.int8
    kw = dict(acc_bits=16, spill_int16=True, interpret=False)
    if mode == "fused":
        fn = lambda x, w, s: ops.int_matmul(x, w, scale=s, **kw)
        shapes = [((M, K), i8), ((K, N), i8), ((N,), f32)]
    elif mode == "requant":
        fn = lambda x, w, s, o: ops.int_matmul(
            x, w, scale=s, out_scale=o, act_fn="gelu", **kw)
        shapes = [((M, K), i8), ((K, N), i8), ((N,), f32), ((), f32)]
    else:
        fn = lambda x, w, s, a: ops.int_matmul(x, w, scale=s, aq_scale=a, **kw)
        shapes = [((M, K), f32), ((K, N), i8), ((N,), f32), ((), f32)]
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize(
    "kv,B,NB,MB",
    [("fp", 8, 513, 64), ("int8", 8, 513, 64), ("int4", 8, 513, 64),
     ("int8", 24, 3600, 192)],  # the yi6b-decode-offline cell's decode call
    ids=["fp", "int8", "int4", "int8-cell"],
)
def test_paged_attention_compiles(one_chip, kv, B, NB, MB):
    KV, G, Dh, bs = 4, 8, 128, 16
    pool_dt, Dhp = {"fp": (jnp.bfloat16, Dh), "int8": (jnp.int8, Dh),
                    "int4": (jnp.uint8, Dh // 2)}[kv]
    shapes = [((B, KV * G, Dh), jnp.bfloat16), ((NB, bs, KV, Dhp), pool_dt),
              ((NB, bs, KV, Dhp), pool_dt), ((B, MB), jnp.int32), ((B,), jnp.int32)]
    if kv == "fp":
        fn = lambda q, kp, vp, bt, ln: ops.paged_attention(q, kp, vp, bt, ln, interpret=False)
    else:
        shapes += [((NB, bs, KV), jnp.float32)] * 2
        fn = lambda q, kp, vp, bt, ln, ks, vs: ops.paged_attention(
            q, kp, vp, bt, ln, kps=ks, vps=vs, interpret=False)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv", ["fp", "int8", "int4"])
def test_paged_mla_attention_compiles(one_chip, kv):
    B, H, R, P, bs, NB, MB = 8, 128, 512, 64, 16, 513, 64
    pool_dt, w = {"fp": (jnp.bfloat16, 1), "int8": (jnp.int8, 1),
                  "int4": (jnp.uint8, 2)}[kv]
    shapes = [((B, H, R), jnp.float32), ((B, H, P), jnp.float32),
              ((NB, bs, R // w), pool_dt), ((NB, bs, P // w), pool_dt),
              ((B, MB), jnp.int32), ((B,), jnp.int32)]
    scale = (128 + P) ** -0.5
    if kv == "fp":
        fn = lambda ql, qp, c, k, bt, ln: ops.paged_mla_attention(
            ql, qp, c, k, bt, ln, scale=scale, interpret=False)
    else:
        # the quantized variants also replay the absorb path's 8-bit
        # activation fake-quant in the kernel
        shapes += [((NB, bs), jnp.float32)] * 2 + [((), jnp.float32)]
        fn = lambda ql, qp, c, k, bt, ln, cs, ks, a: ops.paged_mla_attention(
            ql, qp, c, k, bt, ln, ckvs=cs, kpes=ks, scale=scale, aq_scale=a,
            act_bits=8, interpret=False)
    _compile(fn, one_chip, *shapes)


def _attention_grad(attend):
    return jax.value_and_grad(lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                              argnums=(0, 1, 2))


def test_causal_attention_compiles(one_chip):
    """Training attention with its backward at smollm135m-train-a2q's shapes
    (20 rows of 2048 tokens, 9 query heads over 3 KV heads of 64): one
    forward and one of each backward kernel."""
    from repro.dist.census import attention_kernel_census

    B, T, H, KV, Dh = 20, 2048, 9, 3, 64
    fn = _attention_grad(lambda q, k, v: ops.causal_attention(q, k, v, interpret=False))
    text = _compile(fn, one_chip, ((B, T, H, Dh), jnp.bfloat16), ((B, T, KV, Dh), jnp.bfloat16),
                    ((B, T, KV, Dh), jnp.bfloat16))
    assert attention_kernel_census(text) == {"fwd": 1, "dq": 1, "dkv": 1}


def test_causal_attention_compiles_per_chip_heads(topo, monkeypatch):
    """Under yi6b-train-tp4's mesh (data 1 x model 4 on a v5e 2x2; 4 rows of
    4096 tokens, 32 query heads over 4 KV heads of 128) the kernel runs in a
    ``shard_map`` over each chip's 8 query heads and its KV head: no chip
    gathers, exchanges or passes on another's heads."""
    import functools

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.dist.census import attention_kernel_census, collective_census
    from repro.dist.sharding import ShardingRules, make_mesh
    from repro.nn import attention as attn

    arch = get_arch("yi-6b")
    a = arch.stacks[0].attn
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    rules = ShardingRules.default(mesh, arch)
    monkeypatch.setattr(attn, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "causal_attention",
                        functools.partial(ops.causal_attention, interpret=False))
    fn = _attention_grad(lambda q, k, v: attn._causal_kernel(q, k, v, a, mesh, rules))
    heads = NamedSharding(mesh, P(None, None, "model", None))
    B, T = 4, 4096
    args = [jax.ShapeDtypeStruct((B, T, n, a.head_dim), jnp.bfloat16, sharding=heads)
            for n in (a.heads, a.kv_heads, a.kv_heads)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert attention_kernel_census(text) == {"fwd": 1, "dq": 1, "dkv": 1}
    census = collective_census(text)
    assert all(census[k]["count"] == 0
               for k in ("all-gather", "all-to-all", "collective-permute")), census
