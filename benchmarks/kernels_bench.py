"""Kernel microbenchmarks (interpret-mode wall time is NOT TPU-meaningful; the
derived column is the oracle-vs-kernel agreement + the VMEM working-set bytes
each BlockSpec claims, which is the structural number that matters off-TPU).

Every row carries an ``ok`` flag — kernel output checked against its jnp
oracle — and ``main`` exits nonzero when any is False, so the CI
``kernels-smoke`` job fails on any oracle mismatch."""

from __future__ import annotations

import sys

import numpy as np
import jax.numpy as jnp

from benchmarks.common import time_call
from repro.kernels import ops, ref
from repro.roofline import hw


def _vmem_claim(*block_shapes_dtypes) -> int:
    total = 0
    for shape, dtype in block_shapes_dtypes:
        n = 1
        for d in shape:
            n *= d
        total += n * jnp.dtype(dtype).itemsize
    return total


def run() -> dict:
    rng = np.random.default_rng(0)
    rows = []
    print("name,us_per_call,derived")

    # int_matmul: VMEM claim for the (128, 128, 512) tiling
    x = jnp.asarray(rng.integers(-128, 128, (256, 1024)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (1024, 256)), jnp.int8)
    us = time_call(lambda: ops.int_matmul(x, w, block_m=128, block_n=128, block_k=512))
    vm = _vmem_claim(((128, 512), jnp.int8), ((512, 128), jnp.int8), ((128, 128), jnp.int32))
    ok = bool((ops.int_matmul(x, w) == ref.ref_int_matmul(x, w)).all())
    print(f"int_matmul_256x1024x256,{us:.1f},vmem={vm}B fits={vm < hw.VMEM_BYTES} exact={ok}")
    rows.append(dict(name="int_matmul", vmem=vm, ok=ok))

    # fused W8A8 epilogue: per-channel scale + bias folded into the flush —
    # the serve-path layer runs in ONE pallas_call instead of matmul + dequant
    scale = jnp.asarray(rng.uniform(0.001, 0.1, (256,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    us = time_call(lambda: ops.int_matmul(x, w, scale=scale, bias=bias))
    got = ops.int_matmul(x, w, scale=scale, bias=bias)
    want = ref.ref_int_matmul_fused(x, w, scale, bias)
    ok = bool(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-6))
    # scale-only epilogue is bit-exact (one fp32 multiply either way)
    ok &= bool(
        (np.asarray(ops.int_matmul(x, w, scale=scale))
         == np.asarray(ref.ref_int_matmul_fused(x, w, scale))).all()
    )
    print(f"int_matmul_fused_epilogue,{us:.1f},epilogue adds (1,128) f32 scale+bias blocks ok={ok}")
    rows.append(dict(name="int_matmul_fused", ok=ok))

    # int16 spill halves the accumulator scratch — and composes with the
    # fused epilogue (the serve path when A2Q guarantees acc_bits <= 16)
    xs = jnp.asarray(rng.integers(0, 8, (64, 256)), jnp.int8)
    ws = jnp.asarray(rng.integers(-2, 3, (256, 64)), jnp.int8)
    s16 = jnp.asarray(rng.uniform(0.001, 0.1, (64,)), jnp.float32)
    got = ops.int_matmul(xs, ws, acc_bits=16, spill_int16=True, scale=s16, block_k=64)
    want = ref.ref_int_matmul_fused(xs, ws, s16)
    ok = bool((np.asarray(got) == np.asarray(want)).all())
    vm16 = _vmem_claim(((128, 512), jnp.int8), ((512, 128), jnp.int8), ((128, 128), jnp.int16))
    print(f"int_matmul_int16_spill,0.0,scratch {vm - vm16} bytes saved per tile ok={ok}")
    rows.append(dict(name="int16_spill", saved=vm - vm16, ok=ok))

    # requantizing epilogue (int8-out chaining): acc -> rescale -> act replay
    # -> round/clamp -> int8 codes, bit-exact vs the jnp oracle for both pow2
    # and arbitrary out scales (f32 divide either way)
    out_pow2 = jnp.exp2(jnp.asarray(rng.integers(-4, 0, (256,)), jnp.float32))
    out_rand = jnp.asarray(rng.uniform(0.01, 0.3, (256,)), jnp.float32)
    us = time_call(lambda: ops.int_matmul(x, w, scale=scale, bias=bias, out_scale=out_pow2))
    ok = True
    for out_scale, act_fn in ((out_pow2, None), (out_rand, None), (out_pow2, "relu2"),
                              (out_rand, "gelu")):
        got = ops.int_matmul(x, w, scale=scale, bias=bias, out_scale=out_scale,
                             act_fn=act_fn)
        want = ref.ref_int_matmul_requant(x, w, scale, out_scale, bias=bias,
                                          act_fn=act_fn)
        ok &= bool((np.asarray(got) == np.asarray(want)).all())
    print(f"int_matmul_requant_epilogue,{us:.1f},int8-out chaining: pow2+random "
          f"out_scale, relu2/gelu replay, bit-exact={ok}")
    rows.append(dict(name="int_matmul_requant", ok=ok))

    # unsigned-8 symmetrization: u8 codes travel as q-128 and the kernel adds
    # 128*colsum(w) back at flush — exact in int32, so the old N<=7 unsigned
    # restriction on the fused path is gone
    xu = jnp.asarray(rng.integers(0, 256, (64, 256)) - 128, jnp.int8)
    su = jnp.asarray(rng.uniform(0.001, 0.1, (64,)), jnp.float32)
    got = ops.int_matmul(xu, ws, scale=su, in_signed=False, block_k=64)
    offs = 128 * np.asarray(ws, np.int64).sum(axis=0)
    acc = (np.asarray(xu, np.int64) @ np.asarray(ws, np.int64)) + offs
    want = acc.astype(np.float32) * np.asarray(su)[None, :]
    ok = bool((np.asarray(got) == want.astype(np.float32)).all())
    print(f"int_matmul_u8_symmetrize,0.0,offset=128*colsum(w) restores unsigned "
          f"codes exactly ok={ok}")
    rows.append(dict(name="int_matmul_u8_sym", ok=ok))

    # a2q_quantize fused kernel
    v = jnp.asarray(rng.normal(size=(2048, 512)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(512,)) + 3, jnp.float32)
    d = jnp.asarray(rng.normal(size=(512,)) - 6, jnp.float32)
    us = time_call(lambda: ops.a2q_quantize(v, t, d, weight_bits=8, acc_bits=16,
                                            input_bits=8, input_signed=False))
    _, q_got = ops.a2q_quantize(v, t, d, weight_bits=8, acc_bits=16, input_bits=8,
                                input_signed=False)
    _, q_ref = ref.ref_a2q_quantize(v, t, d, 8, 16, 8, False)
    ok = bool((np.asarray(q_got, np.int32) == np.asarray(q_ref)).all())
    vm = _vmem_claim(((512, 256), jnp.float32), ((1, 256), jnp.float32), ((512, 256), jnp.float32),
                     ((512, 256), jnp.int8))
    print(f"a2q_quantize_2048x512,{us:.1f},vmem={vm}B fits={vm < hw.VMEM_BYTES} exact={ok}")
    rows.append(dict(name="a2q_quantize", vmem=vm, ok=ok))

    # paged attention: fp32 blocks and int8 blocks with in-kernel dequant
    B, KV, G, Dh, NB, bs, MB = 4, 2, 4, 64, 32, 8, 6
    H = KV * G
    kp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
    bt_np = np.zeros((B, MB), np.int32)
    lens = [37, 5, 48, 16]
    nxt = 1
    for b, ln in enumerate(lens):
        for j in range(-(-ln // bs)):
            bt_np[b, j] = nxt
            nxt += 1
    bt = jnp.asarray(bt_np)
    ln = jnp.asarray(lens, jnp.int32)
    qd = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    us = time_call(lambda: ops.paged_attention(qd, kp, vp, bt, ln))
    ok = bool(np.allclose(np.asarray(ops.paged_attention(qd, kp, vp, bt, ln)),
                          np.asarray(ref.ref_paged_attention(qd, kp, vp, bt, ln)), atol=2e-5))
    vm = _vmem_claim(((1, bs, 1, Dh), jnp.float32), ((1, bs, 1, Dh), jnp.float32))
    print(f"paged_attention_fp32,{us:.1f},kv_block_vmem={vm}B ok={ok}")
    rows.append(dict(name="paged_attention", vmem=vm, ok=ok))

    kq = jnp.asarray(rng.integers(-127, 128, (NB, bs, KV, Dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (NB, bs, KV, Dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (NB, bs, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (NB, bs, KV)), jnp.float32)
    us = time_call(lambda: ops.paged_attention(qd, kq, vq, bt, ln, kps=ks, vps=vs))
    ok = bool(np.allclose(
        np.asarray(ops.paged_attention(qd, kq, vq, bt, ln, kps=ks, vps=vs)),
        np.asarray(ref.ref_paged_attention_q8(qd, kq, vq, ks, vs, bt, ln)), atol=2e-5,
    ))
    vm8 = _vmem_claim(((1, bs, 1, Dh), jnp.int8), ((1, bs, 1, Dh), jnp.int8),
                      ((1, bs, 1), jnp.float32), ((1, bs, 1), jnp.float32))
    print(f"paged_attention_int8,{us:.1f},kv_block_vmem={vm8}B ({vm}B fp32, "
          f"{vm / vm8:.2f}x less DMA) ok={ok}")
    rows.append(dict(name="paged_attention_q8", vmem=vm8, fp32_vmem=vm, ok=ok))

    # rwkv6 scan state residency
    r = jnp.asarray(rng.normal(size=(4, 64, 64)), jnp.float32)
    wdecay = jnp.asarray(rng.uniform(0.9, 0.999, size=(4, 64, 64)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    args = (r[:, None].reshape(1, 4, 64, 64), r.reshape(1, 4, 64, 64),
            r.reshape(1, 4, 64, 64), wdecay.reshape(1, 4, 64, 64), u)
    us = time_call(lambda: ops.rwkv6_scan(*args, chunk=16))
    y_got, _ = ops.rwkv6_scan(*args, chunk=16)
    y_ref, _ = ref.ref_rwkv6(  # head 0, oracle in its (B, T, D) folded layout
        args[0][:, 0], args[1][:, 0], args[2][:, 0], args[3][:, 0], u[0]
    )
    ok = bool(np.allclose(np.asarray(y_got[:, 0]), np.asarray(y_ref), atol=1e-4))
    vm = _vmem_claim(((64, 64), jnp.float32))
    print(f"rwkv6_scan_T64,{us:.1f},state_vmem={vm}B O(1)-in-T ok={ok}")
    rows.append(dict(name="rwkv6", vmem=vm, ok=ok))
    return {"rows": rows, "all_ok": all(r.get("ok", True) for r in rows)}


def main() -> int:
    out = run()
    bad = [r["name"] for r in out["rows"] if not r.get("ok", True)]
    if bad:
        print(f"ORACLE MISMATCH: {bad}", file=sys.stderr)
        return 1
    print("all kernel oracles OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
