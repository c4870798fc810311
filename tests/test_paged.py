"""Paged-KV serving subsystem: allocator invariants, scheduler policy,
paged-vs-contiguous engine parity, on-device sampling, kernel decode path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models.lm import Runtime, apply_lm, init_cache, init_lm
from repro.nn.module import unbox
from repro.serve.engine import PagedServeEngine, Request, ServeEngine
from repro.serve.paged_cache import PagedKVCache, TRASH_BLOCK
from repro.serve.sampling import SampleConfig, sample_tokens
from repro.serve.scheduler import Scheduler, ServeRequest

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _bound_live_executables_per_test():
    """This module builds dozens of engines, each with its own jitted
    steps; kept alive together until the module ends (conftest's purge),
    their XLA CPU executables made a later compile segfault inside
    ``test_bursty_skewed_wave_completes_under_block_pressure[4]``.
    Purging after every test here bounds them to one test's worth."""
    yield
    jax.clear_caches()


def _params(arch):
    return unbox(init_lm(KEY, arch))


def _greedy_reference(arch, params, prompt, max_new, max_seq=64):
    """Step-by-step single-sequence decode as the oracle."""
    cache = init_cache(arch, 1, max_seq, dtype=jnp.dtype(arch.compute_dtype))
    logits = None
    for pos, t in enumerate(prompt):
        logits, cache, _ = apply_lm(
            params, arch, tokens=jnp.asarray([[t]], jnp.int32), cache=cache,
            start_pos=jnp.asarray(pos, jnp.int32),
        )
    out = []
    pos = len(prompt)
    for _ in range(max_new):
        nxt = int(jnp.argmax(logits[0, 0]))
        out.append(nxt)
        logits, cache, _ = apply_lm(
            params, arch, tokens=jnp.asarray([[nxt]], jnp.int32), cache=cache,
            start_pos=jnp.asarray(pos, jnp.int32),
        )
        pos += 1
    return out


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_orders_blocks_and_recycles():
    arch = reduced(get_arch("yi-6b"))
    cache = PagedKVCache(arch, slots=2, block_size=4, max_seq=32, num_blocks=9)
    cache.allocate(0, 10)  # 3 blocks
    cache.allocate(1, 5)  # 2 blocks
    assert list(cache.tables[0][:3]) == sorted(cache.tables[0][:3])  # logical order
    assert cache.free_blocks == 8 - 5
    assert TRASH_BLOCK not in set(cache.tables[0][:3]) | set(cache.tables[1][:2])
    assert not set(cache.tables[0][:3]) & set(cache.tables[1][:2])  # disjoint
    # growing reuses already-owned blocks first
    cache.allocate(0, 12)  # still 3 blocks
    assert cache.free_blocks == 3
    cache.release(0)
    assert cache.free_blocks == 6
    assert (cache.tables[0] == TRASH_BLOCK).all() and cache.lens[0] == 0
    assert cache.peak_blocks == 5


def test_allocator_exhaustion_and_bounds():
    arch = reduced(get_arch("yi-6b"))
    cache = PagedKVCache(arch, slots=2, block_size=4, max_seq=16, num_blocks=3)
    assert cache.can_allocate(8) and not cache.can_allocate(12)
    cache.allocate(0, 8)
    with pytest.raises(RuntimeError):
        cache.allocate(1, 8)
    with pytest.raises(ValueError):
        cache.allocate(1, 17)  # beyond max_seq


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def _req(uid, n, max_new=4):
    return ServeRequest(uid=uid, prompt=np.arange(n, dtype=np.int32), max_new=max_new)


def test_scheduler_fifo_admission_and_recycling():
    s = Scheduler(2, prefill_chunk=4)
    for i, n in enumerate((5, 3, 7)):
        s.submit(_req(i, n))
    admitted = s.admissions(lambda r: True)
    assert [slot for slot, _ in admitted] == [0, 1]
    assert [r.uid for _, r in admitted] == [0, 1]
    # chunked prefill plan covers the prompt exactly
    chunks = list(s.prefill_plan(0))
    assert [len(c) for c, _ in chunks] == [4, 1] and [st for _, st in chunks] == [0, 4]
    # head-of-queue blocking: nothing admitted when capacity says no
    assert s.admissions(lambda r: False) == []
    # finishing a request frees its slot for the queue
    for tok in range(4):
        done = s.record_token(0, tok)
    assert done and s.slots[0] is None
    assert [r.uid for _, r in s.admissions(lambda r: True)] == [2]


def test_scheduler_lockstep_groups_equal_lengths():
    s = Scheduler(4, prefill_chunk=4, lockstep=True)
    for i, n in enumerate((5, 5, 3, 5)):
        s.submit(_req(i, n))
    group = s.admissions(lambda r: True)
    assert [r.uid for _, r in group] == [0, 1]  # stops at the length change
    assert s.admissions(lambda r: True) == []  # engine busy -> no admission


def test_scheduler_eos_finishes_early_and_frees_slot():
    """Regression: record_token only ever checked max_new — an eos_id was
    never consulted, so real traffic decoded garbage past end-of-sequence
    and burned blocks until the length cap."""
    s = Scheduler(1, prefill_chunk=4)
    r = ServeRequest(uid=0, prompt=np.arange(3, dtype=np.int32), max_new=8, eos_id=42)
    s.submit(r)
    s.admissions(lambda q: True)
    assert not s.record_token(0, 7)
    assert s.record_token(0, 42)  # the EOS emit itself completes the request
    assert r.done and r.generated == [7, 42]
    assert s.slots[0] is None  # slot freed immediately, not at max_new
    assert r.latency >= 0


def test_request_latency_stats_guarded_before_events():
    """Regression: the timestamps defaulted to 0.0, so latency/ttft read on
    an in-flight request returned epoch-scale negative values that percentile
    aggregations would silently swallow; they now refuse instead of lying."""
    r = ServeRequest(uid=0, prompt=np.arange(2, dtype=np.int32), max_new=2)
    with pytest.raises(RuntimeError):
        r.latency
    with pytest.raises(RuntimeError):
        r.ttft
    s = Scheduler(1)
    s.submit(r)
    s.admissions(lambda q: True)
    with pytest.raises(RuntimeError):  # submitted, but no first token yet
        r.ttft
    with pytest.raises(RuntimeError):
        r.latency
    s.record_token(0, 5)
    assert r.ttft >= 0
    s.record_token(0, 6)
    assert r.done and r.latency >= r.ttft >= 0


# ---------------------------------------------------------------------------
# engine parity (the tentpole acceptance gate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["yi-6b", "smollm-135m"])
def test_paged_engine_matches_contiguous_greedy(name):
    """Token-identical greedy outputs, mixed prompt lengths, more requests
    than slots (exercises slot recycling + block reuse).  yi-6b is GQA
    (kv_heads < heads); smollm ties embeddings."""
    arch = reduced(get_arch(name))
    params = _params(arch)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 3, 9, 2)]
    contig = ServeEngine(arch, params, batch=2, max_seq=64)
    want = contig.generate(prompts, max_new=4)
    paged = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4, prefill_chunk=4)
    got = paged.generate(prompts, max_new=4)
    assert got == want
    # every block returned to the free list once the workload drained
    assert paged.cache.free_blocks == paged.cache.num_blocks - 1


def test_paged_engine_mla_matches_reference():
    """MLA latent pools page the same way (deepseek-v3 reduced)."""
    arch = reduced(get_arch("deepseek-v3-671b"))
    params = _params(arch)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (4, 6)]
    paged = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4, prefill_chunk=4)
    got = paged.generate(prompts, max_new=3)
    for p, o in zip(prompts, got):
        assert o == _greedy_reference(arch, params, list(p), 3)


def test_paged_engine_recurrent_continuous_batching():
    """Per-slot isolated prefill makes continuous batching sound for
    recurrent stacks — the seed engine's lockstep restriction is lifted.
    Unequal prompt lengths through fewer slots than requests."""
    arch = reduced(get_arch("rwkv6-7b"))
    params = _params(arch)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 3, 7)]
    paged = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4, prefill_chunk=4)
    got = paged.generate(prompts, max_new=3)
    for p, o in zip(prompts, got):
        assert o == _greedy_reference(arch, params, list(p), 3)


def test_paged_engine_lockstep_fallback():
    arch = reduced(get_arch("hymba-1.5b"))
    params = _params(arch)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, arch.vocab, (6,)).astype(np.int32) for _ in range(2)]
    lock = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4,
                            prefill_chunk=4, lockstep=True)
    got = lock.generate(prompts, max_new=3)
    for p, o in zip(prompts, got):
        assert o == _greedy_reference(arch, params, list(p), 3)


def test_paged_engine_pallas_decode_kernel_path():
    """Runtime(decode_kernel=True) routes decode through the Pallas kernel;
    greedy tokens must match the gathered-view path."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 8)]
    base = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4, prefill_chunk=4)
    want = base.generate(prompts, max_new=3)
    kern = PagedServeEngine(arch, params, batch=2, max_seq=64, block_size=4,
                            prefill_chunk=4, rt=Runtime(decode_kernel=True))
    assert kern.generate(prompts, max_new=3) == want


@pytest.mark.parametrize("name", ["yi-6b", "deepseek-v3-671b"])
def test_int8_kv_parity_bound_vs_fp32(name):
    """int8 KV blocks (kv_quant=True) hold the parity bound against fp32-KV
    greedy decode on the reduced GQA and MLA archs: token-identical wherever
    the fp32 reference's top-2 logit margin exceeds the quantization-noise
    eps; a sub-margin mismatch is a tie and ends that request's comparison.
    The CI serve-smoke job gates the same property through launch/serve."""
    from repro.serve.engine import parity_up_to_ties

    arch = reduced(get_arch(name))
    params = _params(arch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (10, 7, 13, 4)]
    kw = dict(batch=2, max_seq=64, block_size=8, prefill_chunk=8)
    ref_e = PagedServeEngine(arch, params, **kw)
    q8_e = PagedServeEngine(arch, params, kv_quant=True, **kw)
    outs_ref = ref_e.generate(prompts, max_new=6)
    outs_q8 = q8_e.generate(prompts, max_new=6)
    ok, ties, detail = parity_up_to_ties(ref_e.last_requests, outs_q8, eps=0.05)
    assert ok, detail
    # the bound must not be vacuous: most requests decode identically
    exact = sum(a == b for a, b in zip(outs_ref, outs_q8))
    assert exact >= len(prompts) - ties


def test_int8_kv_decode_kernel_matches_gathered_view():
    """The q8 Pallas decode kernel (in-register dequant) and the dequantized
    gathered-view path read the same int8 pools — greedy tokens identical."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 8)]
    kw = dict(batch=2, max_seq=64, block_size=4, prefill_chunk=4, kv_quant=True)
    base = PagedServeEngine(arch, params, **kw)
    want = base.generate(prompts, max_new=4)
    kern = PagedServeEngine(arch, params, rt=Runtime(decode_kernel=True), **kw)
    assert kern.generate(prompts, max_new=4) == want


def test_int8_kv_bytes_per_token_ratio():
    """The headline: int8 pools cut seq-indexed KV bytes/token >= 3x on the
    reduced archs (head_dim=16: (16+4)B vs 64B per head = 3.2x; production
    head dims approach 4x) and the pools really are int8 + fp32 scales."""
    for name in ("yi-6b", "deepseek-v3-671b"):
        arch = reduced(get_arch(name))
        fp = PagedKVCache(arch, 2, block_size=8, max_seq=64, dtype=jnp.float32)
        q8 = PagedKVCache(arch, 2, block_size=8, max_seq=64, dtype=jnp.float32,
                          kv_quant=True)
        ratio = fp.kv_bytes_per_token() / q8.kv_bytes_per_token()
        assert ratio >= 3.0, (name, ratio)
        leaf = q8.pools["0"]["attn"]
        code_key = "kp" if "kp" in leaf else "ckvp"
        scale_key = "kps" if "kps" in leaf else "ckvs"
        assert leaf[code_key].dtype == jnp.int8
        assert leaf[scale_key].dtype == jnp.float32


def test_int8_kv_slot_recycling_resets_scales():
    """A recycled slot's blocks may carry stale int8 codes + scales; the
    allocator hands fresh blocks in logical order and lengths gate reads, so
    a new sequence in a recycled slot decodes exactly like a fresh engine."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(8)
    p1 = [rng.integers(0, arch.vocab, (6,)).astype(np.int32) for _ in range(3)]
    p2 = rng.integers(0, arch.vocab, (9,)).astype(np.int32)
    kw = dict(batch=1, max_seq=64, block_size=4, prefill_chunk=4, kv_quant=True)
    engine = PagedServeEngine(arch, params, **kw)
    engine.generate(p1, max_new=3)  # churn: 3 sequences recycle slot 0
    got = engine.generate([p2], max_new=3)
    fresh = PagedServeEngine(arch, params, **kw)
    assert got == fresh.generate([p2], max_new=3)


def test_paged_engine_empty_prompt_synthesizes_bos():
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    engine = PagedServeEngine(arch, params, batch=2, max_seq=32, block_size=4)
    outs = engine.generate([np.zeros((0,), np.int32)], max_new=2)
    assert len(outs[0]) == 2
    assert outs[0] == _greedy_reference(arch, params, [engine.bos_id], 2)


def test_admission_round_cannot_jointly_overcommit():
    """Two requests that each fit the free pool but not together: the same
    admissions round must admit only the first (round-local budget), stall
    the second, and still serve everything — never crash allocate()."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    # 3 usable blocks; each request needs 2 -> individually yes, jointly no
    engine = PagedServeEngine(arch, params, batch=2, max_seq=32, block_size=4,
                              prefill_chunk=4, num_blocks=4)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, arch.vocab, (6,)).astype(np.int32) for _ in range(2)]
    outs = engine.generate(prompts, max_new=2)
    for p, o in zip(prompts, outs):
        assert o == _greedy_reference(arch, params, list(p), 2, max_seq=32)


def test_paged_engine_admission_stalls_until_blocks_free():
    """More concurrent tokens than blocks: the scheduler must queue the third
    request until a finished one releases its blocks — never crash."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    # 2 slots; blocks for ~2 requests of (6 prompt + 2 new) at block_size 4
    engine = PagedServeEngine(arch, params, batch=2, max_seq=32, block_size=4,
                              prefill_chunk=4, num_blocks=5)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, arch.vocab, (6,)).astype(np.int32) for _ in range(3)]
    outs = engine.generate(prompts, max_new=2)
    assert all(len(o) == 2 for o in outs)
    for p, o in zip(prompts, outs):
        assert o == _greedy_reference(arch, params, list(p), 2, max_seq=32)


# ---------------------------------------------------------------------------
# chunked prefill == token-by-token prefill (cache-view contract)
# ---------------------------------------------------------------------------


def test_chunked_prefill_wider_than_ring_window():
    """A prefill chunk longer than a sliding-window ring maps several tokens
    to the same slot; only the last write may survive (duplicate-scatter
    order is implementation-defined, so earlier ones are dropped up front).
    Regression: chunk 24 > reduced window 16 must equal token-by-token."""
    arch = reduced(get_arch("h2o-danube-1.8b"))
    params = _params(arch)
    prompts = [np.arange(24, dtype=np.int32) % arch.vocab]
    paged = PagedServeEngine(arch, params, batch=1, max_seq=64, block_size=4,
                             prefill_chunk=24)
    got = paged.generate(prompts, max_new=3)
    assert got[0] == _greedy_reference(arch, params, list(prompts[0]), 3)


@pytest.mark.parametrize("name", ["h2o-danube-1.8b", "rwkv6-7b"])
def test_chunked_prefill_matches_stepwise_on_contiguous_cache(name):
    """apply_lm with T > 1 against a cache (ring + recurrent layouts) equals
    feeding the same tokens one at a time."""
    arch = reduced(get_arch(name))
    params = _params(arch)
    toks = np.arange(7, dtype=np.int32) % arch.vocab

    step = init_cache(arch, 1, 32, dtype=jnp.dtype(arch.compute_dtype))
    logits_step = None
    for pos, t in enumerate(toks):
        logits_step, step, _ = apply_lm(
            params, arch, tokens=jnp.asarray([[t]], jnp.int32), cache=step,
            start_pos=jnp.asarray(pos, jnp.int32),
        )

    chunked = init_cache(arch, 1, 32, dtype=jnp.dtype(arch.compute_dtype))
    logits_chunk = None
    for lo in (0, 3):  # chunks of 3 and 4
        hi = lo + 3 if lo == 0 else 7
        logits_chunk, chunked, _ = apply_lm(
            params, arch, tokens=jnp.asarray(toks[None, lo:hi], jnp.int32),
            cache=chunked, start_pos=jnp.asarray(lo, jnp.int32),
        )
    np.testing.assert_allclose(
        np.asarray(logits_chunk[0, -1]), np.asarray(logits_step[0, 0]), atol=1e-4
    )


# ---------------------------------------------------------------------------
# on-device sampling
# ---------------------------------------------------------------------------


def test_sampling_greedy_matches_argmax():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 17)), jnp.float32)
    got = sample_tokens(logits, SampleConfig(), KEY)
    np.testing.assert_array_equal(np.asarray(got), np.argmax(np.asarray(logits), -1))


def test_sampling_topk_stays_in_topk_set():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    cfg = SampleConfig(method="topk", top_k=3, temperature=0.7)
    toks = np.asarray(sample_tokens(logits, cfg, KEY))
    top3 = np.argsort(np.asarray(logits), -1)[:, -3:]
    assert all(t in row for t, row in zip(toks, top3))


def test_sampling_temperature_is_key_deterministic():
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(4, 11)), jnp.float32)
    cfg = SampleConfig(method="temperature", temperature=1.3)
    a = sample_tokens(logits, cfg, KEY)
    b = sample_tokens(logits, cfg, KEY)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        SampleConfig(method="topk", top_k=0)
    with pytest.raises(ValueError):
        SampleConfig(method="nucleus")


def test_sampling_zero_temperature_is_greedy():
    """Regression: temperature 0 divided logits by the 1e-6 floor, inflating
    them to +/-inf and feeding NaN probabilities into jax.random.categorical
    (--temperature 0 decoded garbage); the zero-temperature limit IS argmax."""
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(5, 13)), jnp.float32)
    want = np.argmax(np.asarray(logits), -1)
    for temp in (0.0, 1e-7):
        cfg = SampleConfig(method="temperature", temperature=temp)
        np.testing.assert_array_equal(np.asarray(sample_tokens(logits, cfg, KEY)), want)
    with pytest.raises(ValueError):
        SampleConfig(method="temperature", temperature=-0.5)


def test_sampling_topk_beyond_vocab_is_clamped():
    """Regression: top_k > vocab crashed inside lax.top_k; top-V-of-V is
    plain temperature sampling, so the clamp must sample identically to it."""
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(4, 7)), jnp.float32)
    cfg = SampleConfig(method="topk", top_k=99, temperature=0.8)
    toks = np.asarray(sample_tokens(logits, cfg, KEY))
    assert ((0 <= toks) & (toks < 7)).all()
    plain = np.asarray(sample_tokens(
        logits, SampleConfig(method="temperature", temperature=0.8), KEY))
    np.testing.assert_array_equal(toks, plain)


def test_paged_engine_temperature_sampling_runs():
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    engine = PagedServeEngine(
        arch, params, batch=2, max_seq=32, block_size=4,
        sample=SampleConfig(method="temperature", temperature=0.9), seed=7,
    )
    outs = engine.generate([np.arange(4, dtype=np.int32)] * 2, max_new=3)
    assert all(len(o) == 3 for o in outs)
    assert all(0 <= t < arch.vocab for o in outs for t in o)


# ---------------------------------------------------------------------------
# MLA + int4 decode-kernel engine coverage
# ---------------------------------------------------------------------------


def test_mla_decode_kernel_matches_gathered_view():
    """Runtime(decode_kernel=True) on the MLA arch routes absorbed decode
    through the Pallas latent-attention kernel (scores + PV directly on the
    compressed pools); greedy tokens must match the gathered-view path."""
    arch = reduced(get_arch("deepseek-v3-671b"))
    params = _params(arch)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 8)]
    kw = dict(batch=2, max_seq=64, block_size=4, prefill_chunk=4)
    base = PagedServeEngine(arch, params, **kw)
    want = base.generate(prompts, max_new=4)
    kern = PagedServeEngine(arch, params, rt=Runtime(decode_kernel=True), **kw)
    assert kern.generate(prompts, max_new=4) == want


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_mla_quantized_kv_decode_kernel_matches_gathered_view(kv_bits):
    """int8 / packed-int4 latent pools through the MLA kernel: the
    in-register dequant (+ nibble unpack) and the absorb path's activation
    fake-quant reproduce the gathered dequant path token-for-token."""
    arch = reduced(get_arch("deepseek-v3-671b"))
    params = _params(arch)
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (6, 9)]
    kw = dict(batch=2, max_seq=64, block_size=4, prefill_chunk=4,
              kv_quant=True, kv_bits=kv_bits)
    base = PagedServeEngine(arch, params, **kw)
    want = base.generate(prompts, max_new=4)
    kern = PagedServeEngine(arch, params, rt=Runtime(decode_kernel=True), **kw)
    assert kern.generate(prompts, max_new=4) == want


def test_int4_kv_decode_kernel_matches_gathered_view():
    """The packed-int4 GQA pools ride the decode kernel (PR 5 left them on
    the gathered path): in-register nibble unpack must match the gathered
    dequant path token-for-token."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, arch.vocab, (n,)).astype(np.int32) for n in (5, 8)]
    kw = dict(batch=2, max_seq=64, block_size=4, prefill_chunk=4,
              kv_quant=True, kv_bits=4)
    base = PagedServeEngine(arch, params, **kw)
    want = base.generate(prompts, max_new=4)
    kern = PagedServeEngine(arch, params, rt=Runtime(decode_kernel=True), **kw)
    assert kern.generate(prompts, max_new=4) == want


# ---------------------------------------------------------------------------
# bursty / skewed-wave scheduler robustness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_bursty_skewed_wave_completes_under_block_pressure(decode_steps):
    """The ROADMAP's heavy-traffic shape: one burst of many short prompts
    with a few 3x-long ones mixed in, against a block budget far below the
    wave's total demand.  The admission gate + strict-FIFO scheduler must
    drain the whole wave — no starvation of the long requests, no
    head-of-queue deadlock ("scheduler stalled" raises), and every request
    decodes its full budget with correct greedy tokens.  Swept per-tick and
    fused-megastep."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(44)
    short = [rng.integers(0, arch.vocab, (rng.integers(3, 7),)).astype(np.int32)
             for _ in range(7)]
    long = [rng.integers(0, arch.vocab, (24,)).astype(np.int32) for _ in range(2)]
    # interleave the long prompts mid-wave so they hit the queue head while
    # shorter requests still hold blocks
    prompts = short[:3] + long[:1] + short[3:6] + long[1:] + short[6:]
    engine = PagedServeEngine(
        arch, params, batch=2, max_seq=64, block_size=4, prefill_chunk=4,
        num_blocks=20, decode_steps=decode_steps,  # ~2 live requests' worth
    )
    outs = engine.generate(prompts, max_new=5)
    assert all(len(o) == 5 for o in outs)
    for p, o in zip(prompts, outs):
        assert o == _greedy_reference(arch, params, list(p), 5)


def test_bursty_wave_no_starvation_order():
    """Strict FIFO under pressure: a long request at the queue head must be
    admitted before later short ones finish leapfrogging it forever — its
    first token lands no later than the wave's last admission."""
    arch = reduced(get_arch("yi-6b"))
    params = _params(arch)
    rng = np.random.default_rng(45)
    long_p = rng.integers(0, arch.vocab, (20,)).astype(np.int32)
    shorts = [rng.integers(0, arch.vocab, (4,)).astype(np.int32) for _ in range(5)]
    engine = PagedServeEngine(
        arch, params, batch=1, max_seq=64, block_size=4, prefill_chunk=4,
        num_blocks=12,
    )
    outs = engine.generate([long_p] + shorts, max_new=3)
    assert outs[0] == _greedy_reference(arch, params, list(long_p), 3)
    assert all(len(o) == 3 for o in outs)
