"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_attention import compute_block_pages, kv_block_range

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# int_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N", [(8, 16, 8), (65, 200, 77), (128, 512, 128), (33, 129, 257)])
@pytest.mark.parametrize("mode", ["exact", "wrap", "saturate"])
def test_int_matmul_matches_ref(M, K, N, mode):
    x = jnp.asarray(RNG.integers(-128, 128, (M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-128, 128, (K, N)), jnp.int8)
    got = ops.int_matmul(x, w, acc_bits=16, mode=mode, block_k=128)
    want = ref.ref_int_matmul(x, w, acc_bits=16, mode=mode, block_k=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("acc_bits", [12, 16, 20, 32])
def test_int_matmul_acc_bits(acc_bits):
    x = jnp.asarray(RNG.integers(-16, 16, (32, 96)), jnp.int8)
    w = jnp.asarray(RNG.integers(-16, 16, (96, 48)), jnp.int8)
    for mode in ("wrap", "saturate"):
        got = ops.int_matmul(x, w, acc_bits=acc_bits, mode=mode, block_k=32)
        want = ref.ref_int_matmul(x, w, acc_bits=acc_bits, mode=mode, block_k=32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_matmul_int16_spill_lossless_under_a2q_bound():
    """The A2Q-enabled kernel optimization: P<=16 guarantees the int16 carry
    is exact."""
    # weights with per-column l1 * input max <= 2^15-1  (the Eq. 15 budget)
    w = jnp.asarray(RNG.integers(-2, 3, (256, 64)), jnp.int8)
    x = jnp.asarray(RNG.integers(0, 8, (64, 256)), jnp.int8)
    got = ops.int_matmul(x, w, acc_bits=16, mode="exact", spill_int16=True, block_k=64)
    want = ref.ref_int_matmul(x, w, acc_bits=32, mode="exact")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int16_spill_rejected_for_wide_acc():
    x = jnp.zeros((8, 8), jnp.int8)
    w = jnp.zeros((8, 8), jnp.int8)
    with pytest.raises(ValueError):
        ops.int_matmul(x, w, acc_bits=24, spill_int16=True)


# -- fused epilogue (the W8A8 serve path) -----------------------------------


@pytest.mark.parametrize("M,K,N", [(5, 33, 7), (65, 200, 77), (8, 16, 8), (1, 129, 257)])
def test_int_matmul_fused_epilogue_matches_ref(M, K, N):
    """Non-block-multiple shapes through the fused epilogue: padded columns
    are sliced off before the caller ever sees them, and the scale-only form
    is bit-exact against the oracle (with bias: 1-ulp, FMA contraction)."""
    x = jnp.asarray(RNG.integers(-64, 64, (M, K)), jnp.int8)
    w = jnp.asarray(RNG.integers(-64, 64, (K, N)), jnp.int8)
    s = jnp.asarray(RNG.uniform(0.01, 2.0, (N,)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(N,)), jnp.float32)
    got_s = ops.int_matmul(x, w, scale=s, block_k=64)
    np.testing.assert_array_equal(
        np.asarray(got_s), np.asarray(ref.ref_int_matmul_fused(x, w, s))
    )
    got_b = ops.int_matmul(x, w, scale=s, bias=b, block_k=64)
    np.testing.assert_allclose(
        np.asarray(got_b), np.asarray(ref.ref_int_matmul_fused(x, w, s, b)), rtol=1e-6
    )


def test_int_matmul_epilogue_vs_matmul_then_scale():
    """Epilogue-vs-(matmul -> scale) parity: the fused op must equal the
    unfused int32 kernel output rescaled outside — same accumulator, the
    epilogue only moves the multiply into the flush."""
    x = jnp.asarray(RNG.integers(-32, 32, (47, 130)), jnp.int8)
    w = jnp.asarray(RNG.integers(-32, 32, (130, 19)), jnp.int8)
    s = jnp.asarray(RNG.uniform(0.01, 1.0, (19,)), jnp.float32)
    fused = ops.int_matmul(x, w, scale=s, block_k=64)
    unfused = ops.int_matmul(x, w, block_k=64).astype(jnp.float32) * s[None, :]
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))
    # scalar scale broadcasts like a full column vector
    sc = jnp.float32(0.125)
    fused_sc = ops.int_matmul(x, w, scale=sc, block_k=64)
    np.testing.assert_array_equal(
        np.asarray(fused_sc),
        np.asarray(ops.int_matmul(x, w, block_k=64), np.float32) * 0.125,
    )


def test_int_matmul_spill_int16_saturate_combo():
    """int16 spill composes with saturate-mode accumulator emulation: the
    saturated carry is always within acc_bits <= 16, so the narrow register
    stays lossless and the tile schedule must match the oracle's replay."""
    x = jnp.asarray(RNG.integers(-8, 8, (32, 96)), jnp.int8)
    w = jnp.asarray(RNG.integers(-8, 8, (96, 48)), jnp.int8)
    for acc_bits in (12, 16):
        got = ops.int_matmul(
            x, w, acc_bits=acc_bits, mode="saturate", spill_int16=True, block_k=32
        )
        want = ref.ref_int_matmul(x, w, acc_bits=acc_bits, mode="saturate", block_k=32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ...and with the fused epilogue on top (the deployed-layer configuration)
    s = jnp.asarray(RNG.uniform(0.01, 1.0, (48,)), jnp.float32)
    got = ops.int_matmul(
        x, w, acc_bits=16, mode="saturate", spill_int16=True, scale=s, block_k=32
    )
    want = ref.ref_int_matmul_fused(x, w, s, acc_bits=16, mode="saturate", block_k=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_matmul_bias_requires_scale():
    x = jnp.zeros((8, 8), jnp.int8)
    with pytest.raises(ValueError):
        ops.int_matmul(x, x, bias=jnp.zeros((8,), jnp.float32))


# ---------------------------------------------------------------------------
# a2q_quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K,C", [(300, 130), (512, 256), (17, 5), (1024, 64)])
@pytest.mark.parametrize("acc_bits,input_signed", [(16, False), (20, True), (12, False)])
def test_a2q_quantize_kernel(K, C, acc_bits, input_signed):
    v = jnp.asarray(RNG.normal(size=(K, C)), jnp.float32)
    t = jnp.asarray(RNG.normal(size=(C,)) + 3, jnp.float32)
    d = jnp.asarray(RNG.normal(size=(C,)) - 6, jnp.float32)
    deq, q = ops.a2q_quantize(
        v, t, d, weight_bits=8, acc_bits=acc_bits, input_bits=8, input_signed=input_signed
    )
    deq_r, q_r = ref.ref_a2q_quantize(v, t, d, 8, acc_bits, 8, input_signed)
    np.testing.assert_array_equal(np.asarray(q, np.int32), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(deq), np.asarray(deq_r), atol=1e-6)


def test_a2q_quantize_kernel_budget_invariant():
    from repro.core.bounds import l1_budget

    v = jnp.asarray(RNG.normal(size=(640, 256)), jnp.float32)
    t = jnp.asarray(RNG.normal(size=(256,)) + 6, jnp.float32)  # over the cap
    d = jnp.asarray(RNG.normal(size=(256,)) - 5, jnp.float32)
    _, q = ops.a2q_quantize(v, t, d, weight_bits=8, acc_bits=14, input_bits=8, input_signed=False)
    l1 = np.abs(np.asarray(q, np.int64)).sum(0)
    assert (l1 <= l1_budget(14, 8, False)).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Tq,Tk,causal,window", [
    (100, 100, True, None),
    (100, 100, True, 17),
    (64, 64, False, None),
    (1, 100, True, None),     # decode
    (1, 100, True, 32),       # windowed decode
    (96, 128, True, None),    # Tq < Tk end-aligned
])
def test_flash_attention_vs_ref(Tq, Tk, causal, window):
    B, H, D = 2, 3, 64
    q = jnp.asarray(RNG.normal(size=(B, H, Tq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, Tk, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, Tk, D)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, block_q=32, block_k=32)
    want = ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, H, T, D = 1, 2, 48, 32
    q = jnp.asarray(RNG.normal(size=(B, H, T, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, H, T, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, H, T, D)), dtype)
    got = ops.flash_attention(q, k, v, block_q=16, block_k=16)
    want = ref.ref_flash_attention(q, k, v)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


# ---------------------------------------------------------------------------
# paged attention (decode through block tables)
# ---------------------------------------------------------------------------


def _paged_setup(B, KV, Dh, NB, bs, MB, lens, seed=0):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
    bt = np.zeros((B, MB), np.int32)
    nxt = 1  # block 0 = trash
    for b, ln in enumerate(lens):
        for j in range(-(-ln // bs)):
            bt[b, j] = nxt
            nxt += 1
    assert nxt <= NB
    return kp, vp, jnp.asarray(bt), jnp.asarray(np.asarray(lens, np.int32))


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (6, 1)])  # MHA, GQA, MQA
def test_paged_attention_matches_ref(H, KV):
    B, Dh, NB, bs, MB = 3, 32, 16, 8, 4
    lens = [19, 1, 32]
    kp, vp, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, ln)
    want = ref.ref_paged_attention(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_matches_contiguous_flash_ref():
    """A fully-packed paged layout is plain causal decode: the kernel must
    agree with the dense attention oracle on the gathered view."""
    B, H, Dh, bs, MB = 2, 4, 16, 4, 3
    L = bs * MB
    kp, vp, bt, ln = _paged_setup(B, H, Dh, 1 + B * MB, bs, MB, [L, L], seed=3)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, ln)
    k = np.asarray(kp)[np.asarray(bt)].reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    v = np.asarray(vp)[np.asarray(bt)].reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    want = ref.ref_flash_attention(
        jnp.asarray(q)[:, :, None, :], jnp.asarray(k), jnp.asarray(v), causal=True
    )[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_ignores_trash_entries():
    """Table entries past a row's length may point at any block (dead slots
    point at trash): they must not leak into the output."""
    B, H, Dh, NB, bs, MB = 2, 2, 16, 8, 4, 4
    kp, vp, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [6, 6], seed=4)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    base = np.asarray(ops.paged_attention(q, kp, vp, bt, ln))
    bt2 = np.asarray(bt).copy()
    bt2[:, 2:] = 7  # garbage beyond the 6-token prefix
    redirected = np.asarray(ops.paged_attention(q, kp, vp, jnp.asarray(bt2), ln))
    np.testing.assert_array_equal(base, redirected)
    # zero-length rows produce zeros, not NaNs
    z = np.asarray(ops.paged_attention(q, kp, vp, bt, jnp.asarray([0, 6], jnp.int32)))
    assert np.isfinite(z).all() and np.abs(z[0]).max() == 0.0


def _q8_pools(rng, NB, bs, KV, Dh):
    kq = jnp.asarray(rng.integers(-127, 128, (NB, bs, KV, Dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (NB, bs, KV, Dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs, KV)), jnp.float32)
    return kq, vq, ks, vs


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (6, 1)])  # MHA, GQA, MQA
def test_paged_attention_q8_matches_ref(H, KV):
    """int8 pools with in-kernel dequant against the jnp q8 oracle."""
    B, Dh, NB, bs, MB = 3, 32, 16, 8, 4
    lens = [19, 1, 32]
    rng = np.random.default_rng(7)
    kq, vq, ks, vs = _q8_pools(rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs)
    want = ref.ref_paged_attention_q8(q, kq, vq, ks, vs, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_q8_equals_dequantized_fp32_path():
    """In-kernel dequant is the same arithmetic as dequantizing the pools
    up front and running the fp32 kernel — the scales commute with the
    block gather."""
    B, H, Dh, NB, bs, MB = 2, 4, 16, 8, 4, 3
    rng = np.random.default_rng(9)
    kq, vq, ks, vs = _q8_pools(rng, NB, bs, H, Dh)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [9, 12])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs)
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    want = ops.paged_attention(q, kd, vd, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_q8_ignores_trash_and_zero_rows():
    B, H, Dh, NB, bs, MB = 2, 2, 16, 8, 4, 4
    rng = np.random.default_rng(11)
    kq, vq, ks, vs = _q8_pools(rng, NB, bs, H, Dh)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [6, 6])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    base = np.asarray(ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs))
    bt2 = np.asarray(bt).copy()
    bt2[:, 2:] = 7
    redirected = np.asarray(
        ops.paged_attention(q, kq, vq, jnp.asarray(bt2), ln, kps=ks, vps=vs)
    )
    np.testing.assert_array_equal(base, redirected)
    z = np.asarray(
        ops.paged_attention(q, kq, vq, bt, jnp.asarray([0, 6], jnp.int32), kps=ks, vps=vs)
    )
    assert np.isfinite(z).all() and np.abs(z[0]).max() == 0.0


def test_paged_attention_scale_args_must_pair():
    B, H, Dh, NB, bs, MB = 1, 2, 16, 4, 4, 2
    rng = np.random.default_rng(13)
    kq, vq, ks, _ = _q8_pools(rng, NB, bs, H, Dh)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [4])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    with pytest.raises(ValueError):
        ops.paged_attention(q, kq, vq, bt, ln, kps=ks)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,chunk", [(50, 16), (64, 64), (33, 8)])
def test_rwkv6_kernel_vs_ref(T, chunk):
    B, H, Dk, Dv = 2, 2, 16, 16
    r = jnp.asarray(RNG.normal(size=(B, H, T, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, T, Dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, T, Dv)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.5, 0.999, size=(B, H, T, Dk)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, Dk)), jnp.float32)
    y, sT = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    for h in range(H):
        y_r, s_r = ref.ref_rwkv6(r[:, h], k[:, h], v[:, h], w[:, h], u[h])
        np.testing.assert_allclose(np.asarray(y[:, h]), np.asarray(y_r), atol=1e-4)
        np.testing.assert_allclose(np.asarray(sT[:, h]), np.asarray(s_r), atol=1e-4)


def test_rwkv6_kernel_initial_state_carry():
    B, H, T, Dk = 1, 1, 32, 8
    r = jnp.asarray(RNG.normal(size=(B, H, T, Dk)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, T, Dk)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, T, Dk)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.7, 0.99, size=(B, H, T, Dk)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, Dk)), jnp.float32)
    # run in two halves, carrying state, must equal the single pass
    y_full, s_full = ops.rwkv6_scan(r, k, v, w, u, chunk=8)
    y1, s1 = ops.rwkv6_scan(r[:, :, :16], k[:, :, :16], v[:, :, :16], w[:, :, :16], u, chunk=8)
    y2, s2 = ops.rwkv6_scan(
        r[:, :, 16:], k[:, :, 16:], v[:, :, 16:], w[:, :, 16:], u,
        initial_state=s1, chunk=8,
    )
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 2)), np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


# ---------------------------------------------------------------------------
# windowed paged-attention decode (sliding-window kernel coverage)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("H,KV,window", [(4, 4, 8), (8, 2, 5), (6, 1, 16)])
def test_paged_attention_window_matches_ref(H, KV, window):
    """Sliding-window masking in the paged decode kernel: each row attends
    only keys at kpos >= length - window.  MHA/GQA/MQA sweep, mixed lengths
    shorter and longer than the window."""
    B, Dh, NB, bs, MB = 3, 32, 16, 8, 4
    lens = [19, 3, 32]
    kp, vp, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens, seed=11)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, ln, window=window)
    want = ref.ref_paged_attention(q, kp, vp, bt, ln, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_window_matches_flash_window_ref():
    """Cross-oracle: a fully-packed windowed paged decode equals the dense
    flash oracle's sliding-window decode on the gathered view."""
    B, H, Dh, bs, MB, W = 2, 4, 16, 4, 3, 5
    L = bs * MB
    kp, vp, bt, ln = _paged_setup(B, H, Dh, 1 + B * MB, bs, MB, [L, L], seed=12)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, ln, window=W)
    k = np.asarray(kp)[np.asarray(bt)].reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    v = np.asarray(vp)[np.asarray(bt)].reshape(B, L, H, Dh).transpose(0, 2, 1, 3)
    want = ref.ref_flash_attention(
        jnp.asarray(q)[:, :, None, :], jnp.asarray(k), jnp.asarray(v),
        causal=True, window=W,
    )[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_window_wider_than_length_is_causal():
    """A window covering the whole sequence must equal the unwindowed path
    (the mask reduces to plain causal validity)."""
    B, H, Dh, NB, bs, MB = 2, 2, 16, 8, 4, 4
    kp, vp, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [7, 13], seed=13)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    wide = ops.paged_attention(q, kp, vp, bt, ln, window=1000)
    plain = ops.paged_attention(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(plain), atol=1e-6)
    with pytest.raises(ValueError):
        ops.paged_attention(q, kp, vp, bt, ln, window=0)


def test_paged_attention_q8_window_matches_ref():
    """Window masking composes with the int8 in-register dequant path."""
    B, H, KV, Dh, NB, bs, MB, W = 2, 4, 2, 16, 10, 4, 4, 6
    rng = np.random.default_rng(14)
    kq, vq, ks, vs = _q8_pools(rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, [9, 14], seed=14)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs, window=W)
    want = ref.ref_paged_attention_q8(q, kq, vq, ks, vs, bt, ln, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

# ---------------------------------------------------------------------------
# packed int4 paged-attention decode (nibble pools, in-register unpack)
# ---------------------------------------------------------------------------


def _pack_nibbles_np(codes):
    u = codes.astype(np.uint8) & 0xF
    return (u[..., 0::2] | (u[..., 1::2] << 4)).astype(np.uint8)


def _q4_pools(rng, NB, bs, KV, Dh):
    kc = rng.integers(-7, 8, (NB, bs, KV, Dh)).astype(np.int8)
    vc = rng.integers(-7, 8, (NB, bs, KV, Dh)).astype(np.int8)
    ks = jnp.asarray(rng.uniform(0.02, 0.2, (NB, bs, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.02, 0.2, (NB, bs, KV)), jnp.float32)
    return jnp.asarray(_pack_nibbles_np(kc)), jnp.asarray(_pack_nibbles_np(vc)), ks, vs


@pytest.mark.parametrize("H,KV", [(4, 4), (8, 2), (6, 1)])  # MHA, GQA, MQA
def test_paged_attention_q4_matches_ref(H, KV):
    """Packed-int4 pools (uint8, half feature width) with in-kernel unpack +
    dequant against the jnp q4 oracle."""
    B, Dh, NB, bs, MB = 3, 32, 16, 8, 4
    lens = [19, 1, 32]
    rng = np.random.default_rng(21)
    kq, vq, ks, vs = _q4_pools(rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens)
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs)
    want = ref.ref_paged_attention_q4(q, kq, vq, ks, vs, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_q4_equals_unpacked_fp32_path():
    """Nibble unpack + rescale in register is the same arithmetic as
    unpacking the pools up front and running the fp32 kernel."""
    B, H, Dh, NB, bs, MB = 2, 4, 16, 8, 4, 3
    rng = np.random.default_rng(22)
    kc = rng.integers(-7, 8, (NB, bs, H, Dh)).astype(np.int8)
    vc = rng.integers(-7, 8, (NB, bs, H, Dh)).astype(np.int8)
    ks = jnp.asarray(rng.uniform(0.02, 0.2, (NB, bs, H)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.02, 0.2, (NB, bs, H)), jnp.float32)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [9, 12])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(
        q, jnp.asarray(_pack_nibbles_np(kc)), jnp.asarray(_pack_nibbles_np(vc)),
        bt, ln, kps=ks, vps=vs,
    )
    kd = jnp.asarray(kc, jnp.float32) * ks[..., None]
    vd = jnp.asarray(vc, jnp.float32) * vs[..., None]
    want = ops.paged_attention(q, kd, vd, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_q4_ignores_trash_and_zero_rows():
    B, H, Dh, NB, bs, MB = 2, 2, 16, 8, 4, 4
    rng = np.random.default_rng(23)
    kq, vq, ks, vs = _q4_pools(rng, NB, bs, H, Dh)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [6, 6])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    base = np.asarray(ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs))
    bt2 = np.asarray(bt).copy()
    bt2[:, 2:] = 7  # garbage beyond the 6-token prefix
    redirected = np.asarray(
        ops.paged_attention(q, kq, vq, jnp.asarray(bt2), ln, kps=ks, vps=vs)
    )
    np.testing.assert_array_equal(base, redirected)
    z = np.asarray(
        ops.paged_attention(q, kq, vq, bt, jnp.asarray([0, 6], jnp.int32), kps=ks, vps=vs)
    )
    assert np.isfinite(z).all() and np.abs(z[0]).max() == 0.0


def test_paged_attention_q4_window_matches_ref():
    """Window masking composes with the packed-int4 unpack path."""
    B, H, KV, Dh, NB, bs, MB, W = 2, 4, 2, 16, 10, 4, 4, 6
    rng = np.random.default_rng(24)
    kq, vq, ks, vs = _q4_pools(rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, [9, 14], seed=24)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kq, vq, bt, ln, kps=ks, vps=vs, window=W)
    want = ref.ref_paged_attention_q4(q, kq, vq, ks, vs, bt, ln, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_attention_q4_requires_scales():
    B, H, Dh, NB, bs, MB = 1, 2, 16, 4, 4, 2
    rng = np.random.default_rng(25)
    kq, vq, _, _ = _q4_pools(rng, NB, bs, H, Dh)
    _, _, bt, ln = _paged_setup(B, H, Dh, NB, bs, MB, [4])
    q = jnp.asarray(RNG.normal(size=(B, H, Dh)), jnp.float32)
    with pytest.raises(ValueError):
        ops.paged_attention(q, kq, vq, bt, ln)


# ---------------------------------------------------------------------------
# the decode kernel's walk: compute blocks of several pages, live pages only
# ---------------------------------------------------------------------------

_WALK = dict(B=8, H=8, KV=4, Dh=16, bs=16, MB=20)  # MB not a multiple of the block


def _walk_lengths(bs, pages, mb):
    """Every boundary of a page and of a compute block, in one batch."""
    return [0, 1, bs - 1, bs, pages * bs - 1, pages * bs, pages * bs + 1, mb * bs]


def _walk_pools(kv, rng, NB, bs, KV, Dh):
    if kv == "fp32":
        kp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(NB, bs, KV, Dh)), jnp.float32)
        return kp, vp, None, None
    return (_q8_pools if kv == "int8" else _q4_pools)(rng, NB, bs, KV, Dh)


def _walk_ref(kv, q, kp, vp, ks, vs, bt, ln, window):
    if kv == "fp32":
        return ref.ref_paged_attention(q, kp, vp, bt, ln, window=window)
    oracle = ref.ref_paged_attention_q8 if kv == "int8" else ref.ref_paged_attention_q4
    return oracle(q, kp, vp, ks, vs, bt, ln, window=window)


@pytest.mark.parametrize("KV", [4, 2])
@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("kv", ["fp32", "int8", "int4"])
def test_paged_attention_walk_boundaries(kv, window, KV):
    """Rows at every page and compute-block boundary, in one batch, over a
    table whose width is not a multiple of the block: the kernel walks only
    each row's live blocks (with ``window``, not those wholly before it)
    and matches the oracle.  KV 4 reads int8 codes as packed words."""
    B, H, Dh, bs, MB = (_WALK[k] for k in ("B", "H", "Dh", "bs", "MB"))
    width, dtype = (Dh // 2, jnp.uint8) if kv == "int4" else (Dh, {"fp32": jnp.float32}.get(kv, jnp.int8))
    pages = compute_block_pages(bs, KV, width, dtype, MB, kv != "fp32")
    assert 1 < pages < MB and MB % pages
    lens = _walk_lengths(bs, pages, MB)
    NB = 1 + sum(-(-n // bs) for n in lens)
    rng = np.random.default_rng(31)
    kp, vp, ks, vs = _walk_pools(kv, rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    got = ops.paged_attention(q, kp, vp, bt, ln, kps=ks, vps=vs, window=window)
    want = _walk_ref(kv, q, kp, vp, ks, vs, bt, ln, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(got)[0]).max() == 0.0  # the zero-length row


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_paged_attention_never_reads_dead_pages(kv):
    """Every pool page that no row's live prefix references holds NaN (fp
    keys and values; int8 scales), the trash block too, and every dead
    table entry points at one.  One row's last live page opens its compute
    block, so the block's later pages are dead.  A page the kernel read
    would turn its row NaN (``0 * NaN`` in the PV product)."""
    B, H, KV, Dh, bs, MB = 4, 8, 4, 16, 16, 20
    pages = compute_block_pages(bs, KV, Dh, {"fp32": jnp.float32, "int8": jnp.int8}[kv], MB, kv == "int8")
    lens = [pages * bs + 3, 0, 5, MB * bs]
    NB = 2 + sum(-(-n // bs) for n in lens)
    rng = np.random.default_rng(37)
    kp, vp, ks, vs = _walk_pools(kv, rng, NB, bs, KV, Dh)
    _, _, bt, ln = _paged_setup(B, KV, Dh, NB, bs, MB, lens)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    want = _walk_ref(kv, q, kp, vp, ks, vs, bt, ln, None)
    live = np.zeros(NB, bool)
    for b, n in enumerate(lens):
        live[np.asarray(bt)[b, : -(-n // bs)]] = True
    dead = jnp.asarray(~live)
    assert not live[0] and not live[NB - 1]
    bt_dead = np.asarray(bt).copy()
    for b, n in enumerate(lens):
        bt_dead[b, -(-n // bs):] = NB - 1
    nan_where = lambda x: jnp.where(dead.reshape(-1, *[1] * (x.ndim - 1)), jnp.nan, x)
    if kv == "fp32":
        kp, vp = nan_where(kp), nan_where(vp)
    else:
        ks, vs = nan_where(ks), nan_where(vs)
    got = np.asarray(ops.paged_attention(q, kp, vp, jnp.asarray(bt_dead), ln, kps=ks, vps=vs))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window", [None, 1, 17, 300])
def test_kv_block_range_boundaries(window):
    """The walk's bounds against a count of the blocks that hold a key the
    row attends, at every boundary length, for ints, numpy and traced
    lengths alike (the engine's counter and the kernel's loop bounds)."""
    bs, pages, mb = 16, 16, 20
    tokens = pages * bs
    lens = _walk_lengths(bs, pages, mb)
    want = []
    for n in lens:
        keys = range(max(n - window, 0) if window else 0, n)
        blocks = sorted({k // tokens for k in keys})
        want.append((blocks[0], blocks[-1] + 1) if blocks else None)
    for n, w in zip(lens, want):
        first, end = kv_block_range(n, tokens, window)
        assert (end <= first) if w is None else ((first, end) == w), (n, first, end, w)
    first, end = kv_block_range(np.asarray(lens), tokens, window)
    traced = jax.jit(lambda x: kv_block_range(x, tokens, window))(jnp.asarray(lens, jnp.int32))
    np.testing.assert_array_equal(end - first, np.asarray(traced[1] - traced[0]))
    assert (end - first).tolist() == [0 if w is None else w[1] - w[0] for w in want]


def test_compute_block_pages_rule():
    """~256 tokens a compute block, clamped to the table; the VMEM budget
    cuts it for wide pools (the padded page counts, not the logical one)."""
    assert compute_block_pages(16, 4, 128, jnp.int8, 192, True) == 16
    assert compute_block_pages(16, 4, 128, jnp.int8, 5, True) == 5
    assert compute_block_pages(8, 2, 16, jnp.float32, 4, False) == 4
    assert compute_block_pages(16, 32, 128, jnp.float32, 192, False) < 16
    assert compute_block_pages(256, 64, 512, jnp.float32, 192, False) == 1


# ---------------------------------------------------------------------------
# MLA latent paged attention (absorbed decode over compressed pools)
# ---------------------------------------------------------------------------

_MLA_SCALE = (48 + 16) ** -0.5  # (qk_nope_dim + qk_rope_dim) ** -0.5


def _mla_setup(rng, B, H, R, P, NB, bs, MB, lens):
    ql = jnp.asarray(rng.normal(size=(B, H, R)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(B, H, P)), jnp.float32)
    bt = np.zeros((B, MB), np.int32)
    nxt = 1
    for b, ln in enumerate(lens):
        for j in range(-(-ln // bs)):
            bt[b, j] = nxt
            nxt += 1
    assert nxt <= NB
    return ql, qp, jnp.asarray(bt), jnp.asarray(np.asarray(lens, np.int32))


def test_paged_mla_attention_matches_ref():
    """fp32 latent pools: kernel vs the gathered latent-softmax oracle,
    mixed lengths including a single-token row."""
    B, H, R, P, NB, bs, MB = 3, 8, 32, 8, 16, 8, 4
    rng = np.random.default_rng(31)
    ql, qp, bt, ln = _mla_setup(rng, B, H, R, P, NB, bs, MB, [19, 1, 32])
    ckvp = jnp.asarray(rng.normal(size=(NB, bs, R)), jnp.float32)
    kpep = jnp.asarray(rng.normal(size=(NB, bs, P)), jnp.float32)
    got = ops.paged_mla_attention(ql, qp, ckvp, kpep, bt, ln, scale=_MLA_SCALE)
    want = ref.ref_paged_mla_attention(ql, qp, ckvp, kpep, bt, ln, scale=_MLA_SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_mla_attention_quantized_matches_ref(bits):
    """int8 / packed-int4 latent pools with per-token scales: in-register
    dequant (and unpack) against the oracle."""
    B, H, R, P, NB, bs, MB = 3, 8, 32, 8, 16, 8, 4
    rng = np.random.default_rng(32 + bits)
    ql, qp, bt, ln = _mla_setup(rng, B, H, R, P, NB, bs, MB, [19, 1, 30])
    if bits == 8:
        ckvp = jnp.asarray(rng.integers(-127, 128, (NB, bs, R)), jnp.int8)
        kpep = jnp.asarray(rng.integers(-127, 128, (NB, bs, P)), jnp.int8)
    else:
        ckvp = jnp.asarray(_pack_nibbles_np(rng.integers(-7, 8, (NB, bs, R)).astype(np.int8)))
        kpep = jnp.asarray(_pack_nibbles_np(rng.integers(-7, 8, (NB, bs, P)).astype(np.int8)))
    ckvs = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs)), jnp.float32)
    kpes = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs)), jnp.float32)
    got = ops.paged_mla_attention(
        ql, qp, ckvp, kpep, bt, ln, ckvs=ckvs, kpes=kpes, scale=_MLA_SCALE
    )
    want = ref.ref_paged_mla_attention(
        ql, qp, ckvp, kpep, bt, ln, ckvs, kpes, scale=_MLA_SCALE
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_mla_attention_act_quant_matches_ref():
    """The in-kernel activation fake-quant (clip(round(x/s)) * s on the
    dequantized latent, the absorb path's A2Q quantizer) matches the oracle
    on both the score and PV uses of the latent."""
    B, H, R, P, NB, bs, MB = 2, 4, 16, 8, 10, 4, 4
    rng = np.random.default_rng(35)
    ql, qp, bt, ln = _mla_setup(rng, B, H, R, P, NB, bs, MB, [9, 14])
    ckvp = jnp.asarray(rng.integers(-127, 128, (NB, bs, R)), jnp.int8)
    kpep = jnp.asarray(rng.integers(-127, 128, (NB, bs, P)), jnp.int8)
    ckvs = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs)), jnp.float32)
    kpes = jnp.asarray(rng.uniform(0.005, 0.05, (NB, bs)), jnp.float32)
    aq = jnp.asarray(0.017, jnp.float32)  # traced scalar, shipped as (1, 1)
    got = ops.paged_mla_attention(
        ql, qp, ckvp, kpep, bt, ln, ckvs=ckvs, kpes=kpes,
        scale=_MLA_SCALE, aq_scale=aq, act_bits=8,
    )
    want = ref.ref_paged_mla_attention(
        ql, qp, ckvp, kpep, bt, ln, ckvs, kpes,
        scale=_MLA_SCALE, aq_scale=aq, act_bits=8,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # act-quant must actually change the result (the flag is load-bearing)
    plain = ops.paged_mla_attention(
        ql, qp, ckvp, kpep, bt, ln, ckvs=ckvs, kpes=kpes, scale=_MLA_SCALE
    )
    assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-6


def test_paged_mla_attention_ignores_trash_and_zero_rows():
    B, H, R, P, NB, bs, MB = 2, 4, 16, 8, 10, 4, 4
    rng = np.random.default_rng(36)
    ql, qp, bt, ln = _mla_setup(rng, B, H, R, P, NB, bs, MB, [6, 6])
    ckvp = jnp.asarray(rng.normal(size=(NB, bs, R)), jnp.float32)
    kpep = jnp.asarray(rng.normal(size=(NB, bs, P)), jnp.float32)
    base = np.asarray(
        ops.paged_mla_attention(ql, qp, ckvp, kpep, bt, ln, scale=_MLA_SCALE)
    )
    bt2 = np.asarray(bt).copy()
    bt2[:, 2:] = 9  # garbage beyond the 6-token prefix
    redirected = np.asarray(
        ops.paged_mla_attention(ql, qp, ckvp, kpep, jnp.asarray(bt2), ln, scale=_MLA_SCALE)
    )
    np.testing.assert_array_equal(base, redirected)
    z = np.asarray(
        ops.paged_mla_attention(
            ql, qp, ckvp, kpep, bt, jnp.asarray([0, 6], jnp.int32), scale=_MLA_SCALE
        )
    )
    assert np.isfinite(z).all() and np.abs(z[0]).max() == 0.0


def test_paged_mla_attention_arg_validation():
    B, H, R, P, NB, bs, MB = 1, 2, 16, 8, 4, 4, 2
    rng = np.random.default_rng(37)
    ql, qp, bt, ln = _mla_setup(rng, B, H, R, P, NB, bs, MB, [4])
    ckvp = jnp.asarray(rng.normal(size=(NB, bs, R)), jnp.float32)
    kpep = jnp.asarray(rng.normal(size=(NB, bs, P)), jnp.float32)
    ckvs = jnp.asarray(rng.uniform(0.01, 0.05, (NB, bs)), jnp.float32)
    with pytest.raises(ValueError):  # scale pools must pair
        ops.paged_mla_attention(ql, qp, ckvp, kpep, bt, ln, ckvs=ckvs, scale=_MLA_SCALE)
    with pytest.raises(ValueError):  # aq_scale and act_bits must pair
        ops.paged_mla_attention(
            ql, qp, ckvp, kpep, bt, ln, scale=_MLA_SCALE, act_bits=8
        )
