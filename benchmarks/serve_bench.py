"""Serving benchmark: contiguous per-token-prefill baseline vs the paged
engine family (fp32 / int8 KV blocks / int8 KV composed with the fused
decode megastep / prefix sharing / speculative decoding) on a mixed-length
workload with a shared-prefix cohort.  ``run_cluster()`` adds the routed
two-replica cluster cohort: capacity scaling vs a single replica and the
mid-wave replica-kill requeue drill (``--cluster`` on the CLI).

Reports continuous-batching throughput (tok/s, split prefill vs decode) and
per-request end-to-end latency p50/p99 for every engine, the paged engine's
peak KV block usage vs the contiguous engine's fixed ``batch x max_seq``
footprint, the KV bytes-per-token the int8 block pools save (~4x), the
prompt tokens the prefix-sharing engine served from shared blocks (plus its
CoW copy count), the speculative engine's acceptance rate, and — per engine —
``dispatches_per_token``: the jitted decode launches each generated token
paid for (1.0 per-tick; ~1/N for the fused megastep engine, which must also
close the paged-vs-contiguous decode gap the per-tick engine regressed).  The int8
engine's greedy tokens are held to the parity bound (token-identical up to
sub-margin quantization ties — see ``launch/serve.py``); the prefix-sharing
and speculative engines must match the plain paged engine token-for-token.
Prints a CSV like the other ``benchmarks/`` modules and returns a headline
dict (``run.py``-aggregatable); ``--json`` writes the same dict to disk.

Wall-clock on CPU/interpret is not TPU-meaningful in absolute terms, but the
*relative* comparisons are structural: the baseline spends one jit call per
prompt token while the paged engine batches whole chunks; the speculative
engine replaces k + 1 decode dispatches with two (a k-step draft scan + one
batched verify); prefix sharing skips recomputing the shared cohort's
common prompt altogether.  Those ratios survive any backend.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.models.lm import Runtime, init_lm
from repro.nn.module import unbox
from repro.obs import percentile
from repro.obs.headroom import engine_headroom
from repro.serve.engine import (
    PagedServeEngine, Request, ServeEngine, deploy_params, parity_up_to_ties,
)
from repro.serve.spec import SpecServeEngine


def _percentiles(reqs) -> dict:
    # nearest-rank percentiles through the shared obs helper — the same math
    # the engines' metrics histograms and the cluster heartbeat report
    lat = [r.latency for r in reqs]
    ttft = [r.ttft for r in reqs]
    return {
        "latency_p50_s": percentile(lat, 50),
        "latency_p99_s": percentile(lat, 99),
        "ttft_p50_s": percentile(ttft, 50),
        "ttft_p99_s": percentile(ttft, 99),
    }


def _stats_row(engine, reqs) -> dict:
    row = engine.throughput()
    row.update(_percentiles(reqs))
    return row


def _drive_contiguous(engine, reqs):
    import time

    for r in reqs:
        r.submitted_at = time.perf_counter()
    if engine.recurrent:
        # the contiguous baseline cannot continuously batch recurrent stacks
        # (slot-at-a-time prefill pollutes every row's non-positional state)
        # and mixed-length prompts rule out multi-request lockstep groups:
        # its honest capability on this workload is one request per group
        for r in reqs:
            engine._generate_lockstep([r])
        return
    pending = list(reqs)
    while pending or any(s is not None for s in engine.slots):
        while pending and engine.admit(pending[0]):
            pending.pop(0)
        if engine.tick() == 0 and not pending:
            break


def _drive_paged(engine, reqs):
    for r in reqs:
        engine.submit(r)
    while not engine.sched.idle():
        engine.step()


def _workload(rng, arch, n, max_new):
    """Mixed-length prompts with a shared-prefix cohort: the regime where
    per-token prefill hurts most and paged memory reuse matters (short and
    long requests share slots).  Half the requests open with one common
    21-token prompt prefix — the common-system-prompt pattern; 21 is
    deliberately NOT a block or chunk multiple, so the chunk-aligned resume
    logic (adopt to the aligned offset, recompute the ragged tail) is
    exercised on every hit rather than only on aligned lengths.  Prompt
    lengths dominate generation lengths, as in real serving traffic."""
    common = rng.integers(0, arch.vocab, (21,)).astype(np.int32)
    lens = rng.integers(8, 49, size=n)
    out = []
    for i, L in enumerate(lens):
        tail = rng.integers(0, arch.vocab, (int(L),)).astype(np.int32)
        prompt = np.concatenate([common, tail[: max(int(L) - 21, 4)]]) if i % 2 else tail
        out.append(Request(uid=i, prompt=prompt, max_new=max_new))
    return out


def run(
    arch_name: str = "yi-6b",
    requests: int = 8,
    max_new: int = 4,
    batch: int = 2,
    max_seq: int = 64,
    block_size: int = 8,
    prefill_chunk: int = 16,
    num_blocks=None,
    decode_steps: int = 8,
    seed: int = 0,
) -> dict:
    arch = reduced(get_arch(arch_name))
    params = unbox(init_lm(jax.random.PRNGKey(seed), arch))
    spec_k = 3
    spec_ok = not any(s.kind in ("rwkv6", "hymba") for s in arch.stacks)

    def workload():  # identical draw for every engine / pass
        return _workload(np.random.default_rng(seed), arch, requests, max_new)

    contig = ServeEngine(arch, params, batch=batch, max_seq=max_seq)
    pkw = dict(batch=batch, max_seq=max_seq, block_size=block_size,
               prefill_chunk=prefill_chunk, num_blocks=num_blocks)
    paged = PagedServeEngine(arch, params, **pkw)
    # the dispatch-count engine: N decode ticks fused per jitted dispatch.
    # Kept separate from `paged` so the per-tick engine remains the reference
    # the int8-KV / prefix-share / spec comparisons were defined against.
    paged_mega = PagedServeEngine(arch, params, decode_steps=decode_steps, **pkw)
    paged_q8 = PagedServeEngine(arch, params, kv_quant=True, **pkw)
    # int8 KV blocks *composed with* the fused decode megastep: the two
    # optimizations must stack (quantized pools ride the same N-tick fused
    # dispatch), not merely coexist in separate engines
    paged_q8m = PagedServeEngine(arch, params, kv_quant=True,
                                 decode_steps=decode_steps, **pkw)
    # the integer fast path and its int8-out chained variant run the deployed
    # artifact (int8 weights + scales).  The chained engine folds activation
    # quantization into the W8A8 kernel (epilogue requant / prologue quant);
    # both share the exact same quantized numerics, so greedy tokens must be
    # identical between them — chaining is a pure dispatch fusion.
    dep = deploy_params(params, arch.quant)
    paged_int = PagedServeEngine(arch, dep, rt=Runtime(int_forward=True), **pkw)
    paged_intc = PagedServeEngine(arch, dep, rt=Runtime(int_chain=True), **pkw)
    paged_px = PagedServeEngine(arch, params, prefix_share=True, **pkw)
    # pin the workload's common system prefix (same rng draw as _workload):
    # prefilled once here, never evicted, so even the *first* shared-cohort
    # request adopts it — the --pin-prompt serving pattern, benchmarked
    common = np.random.default_rng(seed).integers(0, arch.vocab, (21,)).astype(np.int32)
    pinned_tokens = paged_px.pin_prompt(common)
    spec = (SpecServeEngine(arch, params, spec_k=spec_k, **pkw)
            if spec_ok else None)
    engines = [e for e in (contig, paged, paged_mega, paged_q8, paged_q8m,
                           paged_int, paged_intc, paged_px, spec)
               if e is not None]
    # Warmup pass covers every jit shape (the paged engine compiles one
    # prefill per distinct chunk length), so the timed pass measures
    # steady-state serving throughput rather than XLA compile time.
    _drive_contiguous(contig, workload())
    for e in engines[1:]:
        _drive_paged(e, workload())
    for e in engines:
        # one reset path: engine stats, obs (trace + metrics), and — on the
        # paged engines — every cache counter, peak_blocks included
        e.reset_stats()

    reqs_c, reqs_p, reqs_m, reqs_q, reqs_qm, reqs_i, reqs_ic, reqs_x = (
        workload() for _ in range(8))
    _drive_contiguous(contig, reqs_c)
    _drive_paged(paged, reqs_p)
    _drive_paged(paged_mega, reqs_m)
    _drive_paged(paged_q8, reqs_q)
    _drive_paged(paged_q8m, reqs_qm)
    _drive_paged(paged_int, reqs_i)
    _drive_paged(paged_intc, reqs_ic)
    _drive_paged(paged_px, reqs_x)
    reqs_s = None
    if spec is not None:
        reqs_s = workload()
        _drive_paged(spec, reqs_s)

    assert [r.generated for r in reqs_c] == [r.generated for r in reqs_p], \
        "engines diverged on the benchmark workload"
    # the megastep is a pure dispatch fusion: greedy tokens must be identical
    assert [r.generated for r in reqs_m] == [r.generated for r in reqs_p], \
        "megastep engine diverged from per-tick paged decode"
    # ...and it stays a pure fusion over int8 pools: the fused int8 engine
    # must match the per-tick int8 engine token-for-token (both share the
    # same quantized numerics; only the dispatch count differs)
    assert [r.generated for r in reqs_qm] == [r.generated for r in reqs_q], \
        "int8-KV megastep engine diverged from per-tick int8-KV decode"
    # prefix sharing and speculative decoding are lossless: exact parity
    assert [r.generated for r in reqs_x] == [r.generated for r in reqs_p], \
        "prefix-sharing engine diverged"
    if reqs_s is not None:
        assert [r.generated for r in reqs_s] == [r.generated for r in reqs_p], \
            "speculative engine diverged from plain greedy decode"
    # int8-out chaining is a pure dispatch fusion over the integer fast path:
    # the chained engine must match the unchained int engine token-for-token
    assert [r.generated for r in reqs_ic] == [r.generated for r in reqs_i], \
        "int8-chained engine diverged from unchained int-forward decode"
    # int8 KV is lossy: hold it to the parity bound instead of bit equality
    ok, ties, detail = parity_up_to_ties(
        reqs_p, [r.generated for r in reqs_q], eps=0.05
    )
    assert ok, f"int8-KV engine broke the parity bound: {detail}"

    out = {
        "arch": arch_name,
        "requests": requests,
        "contiguous": _stats_row(contig, reqs_c),
        "paged": _stats_row(paged, reqs_p),
        "paged_megastep": _stats_row(paged_mega, reqs_m),
        "decode_steps": decode_steps,
        "paged_int8_kv": _stats_row(paged_q8, reqs_q),
        "paged_megastep_int8_kv": _stats_row(paged_q8m, reqs_qm),
        "paged_int_forward": _stats_row(paged_int, reqs_i),
        "paged_int_forward_chained": _stats_row(paged_intc, reqs_ic),
        "paged_prefix_share": _stats_row(paged_px, reqs_x),
        # fixed lanes vs token-proportional blocks (same dtype, so the slot
        # count ratio is the memory ratio for the seq-indexed leaves)
        "contiguous_cache_slots": batch * max_seq,
        "paged_peak_block_tokens": paged.cache.peak_blocks * paged.cache.block_size,
        # the int8-KV headline: HBM bytes one cached token costs, summed over
        # every seq-indexed pool (codes + scales), fp32 blocks vs int8 blocks
        "kv_bytes_per_token_fp32": paged.cache.kv_bytes_per_token(),
        "kv_bytes_per_token_int8": paged_q8.cache.kv_bytes_per_token(),
        "int8_kv_sub_margin_ties": ties,
        # prefix sharing: prompt tokens served straight from shared blocks
        # (never recomputed) and the CoW copies that kept writers honest
        "prefix_hits": paged_px.cache.prefix_hits,
        "prefix_hit_tokens": paged_px.cache.prefix_hit_tokens,
        "prefix_cow_copies": paged_px.cache.cow_copies,
        "prefix_pinned_tokens": pinned_tokens,
        "prefix_radix_nodes": paged_px.cache.registry_size(),
        "prefix_pool_rebuilds": paged_px.cache.pool_rebuilds,
        "prefix_bt_row_patches": paged_px.cache.bt_row_patches,
        "prefix_bt_full_uploads": paged_px.cache.bt_full_uploads,
    }
    if spec is not None:
        out["spec"] = _stats_row(spec, reqs_s)
        out["spec_k"] = spec_k
        out["spec_acceptance_rate"] = spec.acceptance_rate()
        out["spec_rounds"] = spec.spec_stats["rounds"]
        out["spec_decode_speedup"] = (
            out["spec"]["decode_tok_s"] / out["paged"]["decode_tok_s"]
            if out["paged"]["decode_tok_s"] > 0 else float("inf")
        )
        out["spec_throughput_speedup"] = (
            out["spec"]["tok_s"] / out["paged"]["tok_s"]
            if out["paged"]["tok_s"] > 0 else float("inf")
        )
    # recurrent archs (rwkv6) have no seq-indexed pools at all — nothing to
    # quantize, both byte counts are 0, ratio is the identity
    out["kv_bytes_ratio"] = (
        out["kv_bytes_per_token_fp32"] / out["kv_bytes_per_token_int8"]
        if out["kv_bytes_per_token_int8"] > 0 else 1.0
    )
    out["prefill_speedup"] = (
        out["paged"]["prefill_tok_s"] / out["contiguous"]["prefill_tok_s"]
        if out["contiguous"]["prefill_tok_s"] > 0 else float("inf")
    )
    out["throughput_speedup"] = (
        out["paged"]["tok_s"] / out["contiguous"]["tok_s"]
        if out["contiguous"]["tok_s"] > 0 else float("inf")
    )
    # the megastep headlines (run.py claims): the jitted-dispatch cost each
    # decode token pays, and paged steady-state decode vs the contiguous
    # baseline — the regression this engine exists to close (per-tick paged
    # decode paid per-token host work the contiguous loop never did)
    out["megastep_dispatches_per_token"] = out["paged_megastep"]["dispatches_per_token"]
    out["paged_decode_ratio"] = (
        out["paged_megastep"]["decode_tok_s"] / out["contiguous"]["decode_tok_s"]
        if out["contiguous"]["decode_tok_s"] > 0 else float("inf")
    )
    out["megastep_decode_speedup"] = (
        out["paged_megastep"]["decode_tok_s"] / out["paged"]["decode_tok_s"]
        if out["paged"]["decode_tok_s"] > 0 else float("inf")
    )
    # steady-state decode throughput of int8 blocks vs fp32 blocks: on TPU
    # this is the ~4x-bandwidth win; on CPU/interpret it only proves the
    # quantize/dequant work does not sink the decode path
    out["int8_kv_decode_ratio"] = (
        out["paged_int8_kv"]["decode_tok_s"] / out["paged"]["decode_tok_s"]
        if out["paged"]["decode_tok_s"] > 0 else float("inf")
    )
    # the composed engine (int8 pools + fused megastep): dispatch cost per
    # token must match the fp32 megastep (~1/N), and its steady-state decode
    # must not fall behind the per-tick int8 engine it fuses
    out["int8_kv_megastep_dispatches_per_token"] = (
        out["paged_megastep_int8_kv"]["dispatches_per_token"]
    )
    out["int8_kv_megastep_decode_ratio"] = (
        out["paged_megastep_int8_kv"]["decode_tok_s"]
        / out["paged_int8_kv"]["decode_tok_s"]
        if out["paged_int8_kv"]["decode_tok_s"] > 0 else float("inf")
    )
    # int8-out chaining headlines (run.py claims): the chained engine must
    # launch ZERO standalone act-quant dispatches for deployed layers (the
    # stats-contract field, trace-time count of apply_linear call sites), and
    # folding the quantizer into the kernel must not slow steady-state decode
    # vs the unchained integer fast path
    out["int_chain_requant_dispatches"] = (
        out["paged_int_forward_chained"]["int_chain_requant_dispatches"]
    )
    out["int_chain_decode_ratio"] = (
        out["paged_int_forward_chained"]["decode_tok_s"]
        / out["paged_int_forward"]["decode_tok_s"]
        if out["paged_int_forward"]["decode_tok_s"] > 0 else float("inf")
    )
    # accumulator-headroom telemetry from the deployed integer engine (run.py
    # claims): max static L1 utilization must stay < 1.0 (the A2Q guarantee,
    # Eq. 11) with zero violations across static and observed samples
    hr = engine_headroom(paged_int)
    out["acc_headroom_util_max"] = hr["util_max"]
    out["acc_headroom_observed_frac_max"] = hr["observed_frac_max"]
    out["acc_headroom_violations"] = hr["violations"]
    out["acc_headroom_layers"] = hr["layers"]
    # the prefix-share cliff gate: prefill-dominated latency (TTFT p50) of
    # the sharing engine vs plain paged on the identical workload.  The seed
    # regression was ~13x (a recompile per distinct shared-prefix length);
    # chunk-aligned resume keeps this ~1x (run.py claims <= 1.2)
    out["prefix_share_prefill_ratio"] = (
        out["paged_prefix_share"]["ttft_p50_s"] / out["paged"]["ttft_p50_s"]
        if out["paged"]["ttft_p50_s"] > 0 else float("inf")
    )

    print("engine,tok_s,prefill_tok_s,decode_tok_s,dispatches_per_token,"
          "latency_p50_s,latency_p99_s")
    rows = ["contiguous", "paged", "paged_megastep", "paged_int8_kv",
            "paged_megastep_int8_kv", "paged_int_forward",
            "paged_int_forward_chained", "paged_prefix_share"]
    if "spec" in out:
        rows.append("spec")
    for name in rows:
        r = out[name]
        print(f"{name},{r['tok_s']:.1f},{r['prefill_tok_s']:.1f},{r['decode_tok_s']:.1f},"
              f"{r['dispatches_per_token']:.3f},"
              f"{r['latency_p50_s']:.3f},{r['latency_p99_s']:.3f}")
    print(f"prefill_speedup,{out['prefill_speedup']:.2f},throughput_speedup,"
          f"{out['throughput_speedup']:.2f}")
    print(f"megastep,decode_steps {out['decode_steps']},"
          f"dispatches_per_token {out['megastep_dispatches_per_token']:.3f},"
          f"decode_speedup_vs_tick {out['megastep_decode_speedup']:.2f},"
          f"decode_ratio_vs_contiguous {out['paged_decode_ratio']:.2f}")
    print(f"kv_bytes_per_token,{out['kv_bytes_per_token_fp32']}B fp32,"
          f"{out['kv_bytes_per_token_int8']}B int8,ratio {out['kv_bytes_ratio']:.2f}x,"
          f"decode_ratio {out['int8_kv_decode_ratio']:.2f}")
    print(f"int8_kv_megastep,dispatches_per_token "
          f"{out['int8_kv_megastep_dispatches_per_token']:.3f},"
          f"decode_ratio_vs_tick_int8 {out['int8_kv_megastep_decode_ratio']:.2f}")
    print(f"int_chain,standalone_act_quant {out['int_chain_requant_dispatches']},"
          f"folded {out['paged_int_forward_chained']['int_chain_folded']},"
          f"chained {out['paged_int_forward_chained']['int_chain_chained']},"
          f"decode_ratio_vs_unchained {out['int_chain_decode_ratio']:.2f}")
    print(f"obs,headroom_util_max "
          f"{out['acc_headroom_util_max']:.4f},observed_frac_max "
          f"{out['acc_headroom_observed_frac_max']:.4f},violations "
          f"{out['acc_headroom_violations']}")
    print(f"prefix_share,hits {out['prefix_hits']},shared_tokens "
          f"{out['prefix_hit_tokens']},cow_copies {out['prefix_cow_copies']},"
          f"pinned_tokens {out['prefix_pinned_tokens']},"
          f"prefill_ratio {out['prefix_share_prefill_ratio']:.2f}")
    if "spec" in out:
        print(f"spec,k {out['spec_k']},acceptance {out['spec_acceptance_rate']:.2f},"
              f"decode_speedup {out['spec_decode_speedup']:.2f},"
              f"throughput_speedup {out['spec_throughput_speedup']:.2f}")
    return out


def run_cluster(
    arch_name: str = "yi-6b",
    requests: int = 10,
    max_new: int = 6,
    batch: int = 2,
    max_seq: int = 64,
    block_size: int = 8,
    prefill_chunk: int = 16,
    seed: int = 0,
) -> dict:
    """Two-replica routed cluster vs a single replica on the skewed bursty
    wave, plus a mid-wave replica-kill pass.

    Three passes over the identical workload, all through the Router so the
    single-replica baseline pays the same routing overhead: (1) one replica,
    (2) two replicas, (3) two replicas with the busiest one killed mid-wave.
    Throughput is fleet **capacity** — total tokens over the busiest
    replica's engine-measured busy seconds (the multi-host makespan; see
    ``launch/serve_cluster.py``) — because a single-host CI runner
    interleaves replicas on one core and cannot show wall-clock speedup.
    The 2-replica pass must reach >= 1.6x the 1-replica capacity (a routing
    *balance* claim: a router that piles work on one replica fails it), the
    kill pass must complete every request with token-exact output (the
    at-most-once requeue claim), and all passes must match pass 1
    token-for-token.
    """
    from repro.launch.serve_cluster import aggregate_capacity, build_workload
    from repro.serve.cluster import (
        InProcessReplica, ReplicaConfig, Router, make_cluster_configs,
    )
    from repro.serve.cluster.replica import build_engine

    arch = reduced(get_arch(arch_name))
    params = unbox(init_lm(jax.random.PRNGKey(seed), arch))
    base = ReplicaConfig(
        arch=arch_name, reduced=True, seed=seed, batch=batch, max_seq=max_seq,
        block_size=block_size, prefill_chunk=prefill_chunk,
    )
    cfgs = make_cluster_configs(base, replicas=2)
    # one warmed engine per replica, shared across the timed passes (a fresh
    # InProcessReplica handle per pass wraps the same engine, so XLA compiles
    # are paid once here and the timed passes measure steady-state serving)
    engines = {c.name: build_engine(c, params=params) for c in cfgs}
    rng = np.random.default_rng(seed)
    prompts = build_workload(rng, requests, 12, 4, min(arch.vocab, 50))
    for eng in engines.values():
        warm = [Request(uid=i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        _drive_paged(eng, warm)

    def routed_pass(names, kill_after=None):
        for eng in engines.values():
            eng.reset_stats()
        handles = [InProcessReplica(c, engine=engines[c.name])
                   for c in cfgs if c.name in names]
        router = Router(handles)
        rids = [router.submit(p, max_new=max_new) for p in prompts]
        state = {"killed": None}

        def hook(r, step):
            if state["killed"] is not None:
                return
            done = sum(1 for q in r.reqs.values() if q.done)
            if done < kill_after:
                return
            alive = [st for st in r.states.values() if st.alive]
            if len(alive) < 2:
                return
            victim = max(alive, key=lambda st: (len(st.inflight), st.name))
            if victim.inflight:
                r.kill(victim.name)
                state["killed"] = victim.name

        res = router.drain(on_step=hook if kill_after is not None else None)
        outs = [res[r] for r in rids]
        complete = all(q.done and q.emitted for q in router.reqs.values())
        agg = aggregate_capacity(router.collect_stats())
        requeues, deaths = router.requeues, router.deaths
        router.close()
        return outs, agg, requeues, deaths, complete

    outs1, agg1, _, _, _ = routed_pass({cfgs[0].name})
    outs2, agg2, _, _, _ = routed_pass({c.name for c in cfgs})
    assert outs2 == outs1, "2-replica routed output diverged from 1-replica"
    # the kill pass runs last: the victim engine is left with stranded slots
    outs3, _, requeues, deaths, complete = routed_pass(
        {c.name for c in cfgs}, kill_after=max(1, requests // 4))
    assert outs3 == outs1, \
        "requeued requests after the replica kill diverged (duplicate or lost tokens)"

    out = {
        "arch": arch_name,
        "requests": requests,
        "cluster_1rep_tok_s": agg1["agg_tok_s"],
        "cluster_2rep_tok_s": agg2["agg_tok_s"],
        "cluster_busy_s": agg2["busy_s"],
        "cluster_scaling": (agg2["agg_tok_s"] / agg1["agg_tok_s"]
                            if agg1["agg_tok_s"] > 0 else float("inf")),
        "cluster_deaths": deaths,
        "cluster_requeues": requeues,
        # 1.0 iff every request in the kill pass finished with its full,
        # token-exact stream (outs3 equality above guarantees no duplicates)
        "cluster_requeue_complete": float(complete and deaths == 1),
    }
    print("cluster,replicas,agg_tok_s")
    print(f"cluster,1,{out['cluster_1rep_tok_s']:.1f}")
    print(f"cluster,2,{out['cluster_2rep_tok_s']:.1f}")
    print(f"cluster_scaling,{out['cluster_scaling']:.2f},"
          f"requeue_complete,{out['cluster_requeue_complete']:.1f},"
          f"deaths {out['cluster_deaths']},requeues {out['cluster_requeues']}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=8,
                    help="fused decode ticks per dispatch for the megastep engine")
    ap.add_argument("--cluster", action="store_true",
                    help="also run the 2-replica routed cluster cohort")
    ap.add_argument("--json", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = run(
        arch_name=args.arch, requests=args.requests, max_new=args.max_new,
        batch=args.batch, max_seq=args.max_seq, block_size=args.block_size,
        prefill_chunk=args.prefill_chunk, decode_steps=args.decode_steps,
        seed=args.seed,
    )
    if args.cluster:
        out["cluster"] = run_cluster(
            arch_name=args.arch, requests=args.requests, max_new=args.max_new,
            batch=args.batch, max_seq=args.max_seq, block_size=args.block_size,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
        )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")
    return out


if __name__ == "__main__":
    main()
