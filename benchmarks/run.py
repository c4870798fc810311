"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints each module's CSV, then a claims summary asserting the paper's
*relative* claims hold on the synthetic stand-in data (DESIGN.md Sec. 8):

  Fig 2: wraparound collapses below the bound; A2Q holds accuracy; overflow
         rate grows as P shrinks; A2Q overflow events == 0.
  Fig 3: the weight-norm bound is always at least as tight as the data-type
         bound.
  Fig 4: A2Q extends the accumulator Pareto frontier left of what baseline
         QAT can reach, and dominates it.
  Fig 5: sparsity rises monotonically as P falls.
  Fig 6: LUT ordering fixed32 >= dtype-bound >= PTM; A2Q dominates.

``--json [PATH]`` additionally writes a ``BENCH_<date>.json`` perf snapshot
(serve throughput/latency percentiles, kernel VMEM claims + oracle flags, KV
bytes-per-token fp32 vs int8, the claims table) so the perf trajectory of the
repo is recorded PR over PR; CI uploads it as a build artifact.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer training steps")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--json", nargs="?", const="auto", default=None,
                    help="write a BENCH_<date>.json perf snapshot (optionally to PATH)")
    args = ap.parse_args(argv)
    steps = 25 if args.fast else 40
    fig2_steps = 40 if args.fast else 60

    from benchmarks import (
        bounds_table,
        fig2_overflow,
        fig4_pareto,
        fig5_sparsity,
        fig6_resources,
        kernels_bench,
        serve_bench,
    )

    t0 = time.time()
    results = {}
    print("=" * 72)
    print("fig2_overflow (paper Fig. 2 / App. A)")
    print("=" * 72)
    results["fig2"] = fig2_overflow.run(steps=fig2_steps, reorder=True)

    print("=" * 72)
    print("bounds_table (paper Fig. 3)")
    print("=" * 72)
    results["fig3"] = bounds_table.run(samples=300 if args.fast else 1000)

    print("=" * 72)
    print("fig4_pareto (paper Fig. 4)")
    print("=" * 72)
    results["fig4"] = fig4_pareto.run(steps=steps)

    print("=" * 72)
    print("fig5_sparsity (paper Fig. 5)")
    print("=" * 72)
    results["fig5"] = fig5_sparsity.run(steps=steps)

    print("=" * 72)
    print("fig6_resources (paper Fig. 6/7)")
    print("=" * 72)
    results["fig6"] = fig6_resources.run(steps=steps)

    print("=" * 72)
    print("kernel microbenches")
    print("=" * 72)
    results["kernels"] = kernels_bench.run()

    print("=" * 72)
    print("serving bench (paged vs contiguous engines)")
    print("=" * 72)
    # max_new=8 keeps the decode phase long enough that the speculative
    # engine's dispatch-count win (2 per round vs k+1 ticks) is measured
    # above timing noise — at max_new=4 the identical-prefill phase
    # dominates and the end-to-end ratio sits at the claim threshold
    results["serve"] = serve_bench.run(requests=4 if args.fast else 8, max_new=8)

    print("=" * 72)
    print("serving cluster bench (routed replicas, failover drill)")
    print("=" * 72)
    results["cluster"] = serve_bench.run_cluster(requests=8 if args.fast else 10)

    claims = {
        "serve_int8_kv_bytes_3x_plus": results["serve"]["kv_bytes_ratio"] >= 3.0,
        # speculative decoding: measured acceptance > 0; decode tok/s at
        # least plain paged decode (the structural win — 2 dispatches per
        # round vs k+1 ticks — measured with ~1.3-2x margin on CPU); and
        # end-to-end tok/s not regressed (>= 0.9: prefill is identical and
        # dominates the mixed workload, so the end-to-end ratio carries
        # wall-clock noise a shared CI runner can push a few percent either
        # way — the committed BENCH_*.json baseline records the actual
        # measured >= 1.2x)
        "serve_spec_acceptance_positive": results["serve"].get("spec_acceptance_rate", 0) > 0,
        "serve_spec_decode_at_least_paged": results["serve"].get("spec_decode_speedup", 0) >= 1.0,
        "serve_spec_tok_s_not_regressed": results["serve"].get("spec_throughput_speedup", 0) >= 0.9,
        # prefix sharing: the shared cohort's prompt tokens really came from
        # shared blocks (radix prompt cache: adoption skipped recompute) AND
        # the sharing engine's prefill-dominated latency (TTFT p50) stays
        # within 1.2x of plain paged — the PR-6 cliff (a ~13x regression
        # from per-shared-length prefill recompiles + per-block CoW
        # dispatches) must never come back
        "serve_prefix_share_hit_tokens": results["serve"]["prefix_hit_tokens"] > 0,
        "serve_prefix_share_prefill_ratio": results["serve"]["prefix_share_prefill_ratio"] <= 1.2,
        "kernel_oracles_ok": results["kernels"]["all_ok"],
        "fig2_wrap_collapses": results["fig2"]["wrap_collapses"],
        "fig2_a2q_holds_accuracy": results["fig2"]["a2q_holds"],
        "fig2_a2q_beats_wrap_at_low_P": results["fig2"]["a2q_beats_wrap_at_low_P"],
        "fig2_reorder_nondeterministic_under_saturation": not results["fig2"]["reorder_audit"]["order_invariant"],
        "fig3_weight_bound_tighter": results["fig3"]["weight_bound_always_tighter"],
        "fig4_a2q_extends_pareto": results["fig4"]["a2q_extends_pareto_left"],
        "fig4_a2q_dominates": results["fig4"]["a2q_dominates"],
        "fig5_sparsity_monotone": results["fig5"]["sparsity_monotone_up"],
        "fig6_bound_ordering": results["fig6"]["bound_ordering_ok"],
        "fig6_a2q_dominates_fixed32": results["fig6"]["a2q_dominates_fixed32"],
        "serve_paged_prefill_faster": results["serve"]["prefill_speedup"] > 1.0,
        # the decode megastep (N fused ticks per jitted dispatch): each
        # generated token costs well under one dispatch (~1/N + admission
        # tail windows), and the paged engine's steady-state decode is no
        # longer behind the contiguous baseline it replaced (the per-tick
        # engine paid per-token host work — CoW preflight, lens upload,
        # device_get — the contiguous loop never did; 0.95 leaves wall-clock
        # noise room on shared runners, the BENCH_*.json records the margin)
        "serve_decode_dispatches_per_token": results["serve"]["megastep_dispatches_per_token"] <= 0.2,
        "serve_paged_decode_not_slower": results["serve"]["paged_decode_ratio"] >= 0.95,
        # int8 KV composed with the megastep: the fused dispatch count must
        # carry over to quantized pools, and fusing must not cost decode
        # throughput vs the per-tick int8 engine (0.95 = wall-clock noise
        # floor on shared runners; the BENCH_*.json records the margin)
        "serve_int8_megastep_dispatches_per_token":
            results["serve"]["int8_kv_megastep_dispatches_per_token"] <= 0.2,
        "serve_int8_megastep_decode_not_slower":
            results["serve"]["int8_kv_megastep_decode_ratio"] >= 0.95,
        # int8-out chaining: deployed layers pay ZERO standalone act-quant
        # dispatches (every activation quantizer folds into the W8A8 kernel:
        # epilogue requant on chained edges, prologue quant at chain breaks),
        # and the fold must not cost decode throughput vs the unchained
        # integer fast path (0.95 = wall-clock noise floor on shared runners)
        "serve_int_chain_requant_dispatches":
            results["serve"]["int_chain_requant_dispatches"] == 0,
        "serve_int_chain_decode_not_slower":
            results["serve"]["int_chain_decode_ratio"] >= 0.95,
        # observability: the accumulator-headroom telemetry confirms the
        # deployed integer engine serves strictly inside the A2Q guarantee:
        # max static L1 utilization < 1.0, zero violations
        "serve_acc_headroom_max": results["serve"]["acc_headroom_util_max"] < 1.0,
        "serve_acc_headroom_violations":
            results["serve"]["acc_headroom_violations"] == 0,
        # disaggregated cluster: two routed replicas reach >= 1.6x one
        # replica's busy-time capacity (routing balance), and a mid-wave
        # replica kill completes every request token-exactly via requeue
        "serve_cluster_scaling": results["cluster"]["cluster_scaling"] >= 1.6,
        "serve_cluster_requeue_complete":
            results["cluster"]["cluster_requeue_complete"] == 1.0,
    }
    print("=" * 72)
    print("PAPER CLAIMS SUMMARY")
    print("=" * 72)
    failed = []
    for k, v in claims.items():
        print(f"{'PASS' if v else 'FAIL'}  {k}")
        if not v:
            failed.append(k)
    print(f"total {time.time()-t0:.0f}s")
    if args.json_out:
        slim = {k: {kk: vv for kk, vv in v.items() if kk != "rows"} for k, v in results.items()}
        with open(args.json_out, "w") as f:
            json.dump({"claims": claims, "results": slim}, f, indent=1, default=str)
    if args.json:
        date = datetime.date.today().isoformat()
        path = f"BENCH_{date}.json" if args.json == "auto" else args.json
        snapshot = {
            "date": date,
            "fast": args.fast,
            "wall_s": round(time.time() - t0, 1),
            # the perf trajectory: serve throughput/latency + KV bytes/token
            # (fp32 vs int8 blocks) and the kernel VMEM/oracle rows
            "serve": results["serve"],
            "cluster": results["cluster"],
            "kernels": results["kernels"]["rows"],
            # the observability block: the accumulator-headroom guarantee
            # as measured gauges
            "obs": {
                "acc_headroom_util_max": results["serve"]["acc_headroom_util_max"],
                "acc_headroom_observed_frac_max":
                    results["serve"]["acc_headroom_observed_frac_max"],
                "acc_headroom_violations": results["serve"]["acc_headroom_violations"],
                "acc_headroom_layers": results["serve"]["acc_headroom_layers"],
            },
            "claims": claims,
        }
        with open(path, "w") as f:
            json.dump(snapshot, f, indent=1, default=str)
        print(f"wrote perf snapshot {path}")
    if failed:
        print(f"FAILED claims: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
