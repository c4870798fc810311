"""Serving launcher CLI: batched decode with the continuous-batching engines.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 6 --prompt-len 12 --max-new 8 \
        [--paged --block-size 16 --prefill-chunk 32] [--deploy-int8] \
        [--int-forward] [--kv-int8 [--kv-bits 4]] \
        [--prefix-share [--shared-prefix 24] [--pin-prompt 32]] \
        [--spec-k 4 [--spec-draft self-int8|<config>]] \
        [--decode-steps 8] [--eos-id N | --eos-auto] \
        [--sample topk --temperature 0.8 --top-k 40] [--parity-check]

``--paged`` serves through :class:`PagedServeEngine` (block-table KV cache,
chunked prefill, on-device sampling); the default is the contiguous baseline.
``--deploy-int8`` swaps trained A2Q params for int8 weights + scales before
serving (the paper-guaranteed deployment artifact).  ``--int-forward``
(implies ``--deploy-int8``) runs those deployed linears through the fused
W8A8 integer kernel instead of dequant + float dot; ``--kv-int8`` stores the
paged KV pools as integer blocks with per-slot scales (~4x KV bytes/token at
the default ``--kv-bits 8``; ``--kv-bits 4`` packs two codes per byte).
``--prefix-share`` dedups common prompt prefixes through the radix prompt
cache (refcounted copy-on-write blocks, LRU/cost eviction).
``--shared-prefix N`` prepends an N-token common prefix to every request so
the cache has something to hit; ``--pin-prompt N`` additionally prefills an
N-token system preamble once pre-traffic and pins it permanently (never
evicted), so even the first request adopts it.

``--spec-k K`` serves through :class:`SpecServeEngine`: K tokens drafted per
round (default drafter ``self-int8`` — the same weights on the integer fast
path — or a named config, e.g. ``--spec-draft smollm-135m``, as a separate
small draft model), verified in one batched call, greedy output token-
identical to plain decode.  Archs with ring/recurrent state (no rollback)
refuse spec mode cleanly and fall back to plain paged decode.

``--decode-steps N`` fuses N paged decode ticks into one jitted megastep
dispatch (on-device position/EOS bookkeeping; dead rows coast into the trash
block), and ``--eos-id``/``--eos-auto`` stop requests at end-of-sequence
instead of always burning the full ``--max-new`` budget.

``--parity-check`` runs the configured engine AND the float dequant
contiguous baseline greedily on the same workload and fails unless their
outputs are token-identical — the CI serve-smoke/spec-smoke gate, covering
the full integer path (int8 weights, W8A8 matmuls, int8 KV) and the
speculative path against float truth.

Throughput is reported split into prefill and decode (one aggregate tok/s
hides that prefill dominates mixed-length workloads).
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.launch.cache import enable_compile_cache
from repro.models.lm import Runtime, init_lm
from repro.nn.module import unbox
from repro.obs import Obs
from repro.obs.headroom import engine_headroom
from repro.serve.engine import (
    PagedServeEngine, ServeEngine, init_deployed_lm, parity_up_to_ties,
)
from repro.serve.sampling import SampleConfig


# greedy-margin tie tolerance of the int8-KV parity bound (serve/README.md
# "parity bound"): a token may differ from the float reference only where
# the reference's top-2 logit margin is at most this
PARITY_EPS = 0.05


def _spec_report(engine) -> dict:
    """Speculative-decoding stats block (active=False => clean fallback)."""
    out = {
        "active": engine.spec_active() or engine.spec_stats["rounds"] > 0,
        "supported": engine.spec_supported,
        "k": engine.spec_k,
        "acceptance_rate": engine.acceptance_rate(),
        **engine.spec_stats,
    }
    tag = "speculative" if out["supported"] else "speculative UNSUPPORTED (plain fallback)"
    print(f"[{tag}] k={out['k']} rounds={out['rounds']} "
          f"acceptance={out['acceptance_rate']:.2f} bonus={out['bonus']} "
          f"fallback_rounds={out['fallback_rounds']}")
    return out


def _report(tag: str, engine) -> dict:
    tp = engine.throughput()
    print(
        f"[{tag}] prefill: {tp['prefill_tokens']} tok in {tp['prefill_s']:.2f}s "
        f"({tp['prefill_tok_s']:.1f} tok/s) | decode: {tp['decode_tokens']} tok in "
        f"{tp['decode_s']:.2f}s ({tp['decode_tok_s']:.1f} tok/s, "
        f"{tp['decode_dispatches']} dispatches = "
        f"{tp['dispatches_per_token']:.3f}/tok) | overall {tp['tok_s']:.1f} tok/s"
    )
    if "int_chain_requant_dispatches" in tp:
        print(f"[{tag}] chain report: {tp['int_chain_folded']} folded, "
              f"{tp['int_chain_chained']} chained, "
              f"{tp['int_chain_requant_dispatches']} standalone act-quant, "
              f"{tp['int_chain_fallback']} fallback call sites")
    return tp


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's flags, validated, with the implied ones set
    (``--int-chain`` => ``--int-forward`` => ``--deploy-int8``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--deploy-int8", action="store_true")
    ap.add_argument("--int-forward", action="store_true",
                    help="fused W8A8 integer matmuls for deployed layers (implies --deploy-int8)")
    ap.add_argument("--int-chain", action="store_true",
                    help="int8-out chaining: fold activation quantization into "
                         "the W8A8 kernel (epilogue requant on chained edges, "
                         "prologue quant at chain breaks) so deployed layers "
                         "pay zero standalone act-quant dispatches "
                         "(implies --int-forward)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="integer paged KV blocks with per-slot scales")
    ap.add_argument("--kv-bits", type=int, choices=(8, 4), default=8,
                    help="KV code width with --kv-int8 (4 packs two codes per byte)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="dedup common prompt prefixes via the radix prompt cache")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend an N-token common prefix to every request")
    ap.add_argument("--pin-prompt", type=int, default=0,
                    help="prefill an N-token system preamble once and pin it "
                         "in the prompt cache (prepended to every request; "
                         "requires --prefix-share)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens per round (0 = off)")
    ap.add_argument("--spec-draft", default="self-int8",
                    help="drafter: 'self-int8' (same weights, integer fast path) "
                         "or a config name for a small draft model")
    ap.add_argument("--paged", action="store_true", help="serve via PagedServeEngine")
    ap.add_argument("--block-size", type=int, default=16, help="paged KV tokens per block")
    ap.add_argument("--prefill-chunk", type=int, default=32, help="prompt tokens per prefill jit call")
    ap.add_argument("--num-blocks", type=int, default=None, help="paged KV pool size (blocks)")
    ap.add_argument("--decode-kernel", action="store_true",
                    help="route paged decode through the Pallas paged-attention kernel")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="paged decode ticks fused per jitted dispatch (the "
                         "megastep; 1 = per-tick decode)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="end-of-sequence token id: requests finish the step "
                         "they emit it instead of decoding to --max-new")
    ap.add_argument("--eos-auto", action="store_true",
                    help="probe a greedy contiguous run and use the token "
                         "request 0 emits mid-stream as the EOS id — "
                         "guarantees the workload exercises early EOS "
                         "termination (the CI serve-smoke cohort)")
    ap.add_argument("--sample", choices=("greedy", "temperature", "topk"), default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--parity-check", action="store_true",
                    help="run paged AND contiguous engines; fail on any token mismatch")
    ap.add_argument("--parity-eps", type=float, default=None,
                    help="greedy-margin tie tolerance for --parity-check with --kv-int8 "
                         f"(default {PARITY_EPS}; lossless configs always compare exactly)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record request-span traces and write Chrome trace-event "
                         "JSON here (load in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the unified metrics snapshot (engine + cache + "
                         "chain + headroom) to this path")
    ap.add_argument("--json", default=None, help="write the stats report to this path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not args.paged and not args.parity_check:
        wanted = [
            flag for flag, on in (
                ("--sample", args.sample != "greedy"),
                ("--top-k", args.top_k != 0),
                ("--decode-kernel", args.decode_kernel),
                ("--kv-int8", args.kv_int8),
                ("--num-blocks", args.num_blocks is not None),
                ("--spec-k", args.spec_k > 0),
                ("--prefix-share", args.prefix_share),
                ("--shared-prefix", args.shared_prefix > 0),
                ("--pin-prompt", args.pin_prompt > 0),
                ("--decode-steps", args.decode_steps != 1),
            ) if on
        ]
        if wanted:
            ap.error(f"{', '.join(wanted)} only affect the paged engine; add --paged")
    if args.eos_auto and args.eos_id is not None:
        ap.error("--eos-auto derives the EOS id; drop --eos-id")
    if args.pin_prompt > 0 and not args.prefix_share:
        ap.error("--pin-prompt pins into the prompt cache; add --prefix-share")
    if args.kv_bits != 8 and not args.kv_int8:
        ap.error("--kv-bits only affects integer KV blocks; add --kv-int8")
    if args.spec_draft != "self-int8" and args.spec_k == 0:
        ap.error("--spec-draft only affects speculative decoding; add --spec-k")
    if args.spec_k > 0 and args.sample != "greedy":
        ap.error("--spec-k is lossless for greedy decoding only")
    if args.int_chain:
        args.int_forward = True  # chaining is a mode of the integer fast path
    if args.int_forward:
        args.deploy_int8 = True  # the W8A8 path consumes the deployed artifact
    return args


def load_arch(args):
    arch = get_arch(args.arch)
    return reduced(arch) if args.reduced else arch


def load_params(arch, args) -> dict:
    """Random weights from ``--seed``; with ``--deploy-int8`` the deployed
    int8 artifact, built a layer at a time (``init_deployed_lm``) so that a
    published-width model never holds its float weights whole."""
    key = jax.random.PRNGKey(args.seed)
    if args.deploy_int8:
        return init_deployed_lm(key, arch)
    return unbox(init_lm(key, arch))


def make_prompts(arch, args, seed=None):
    """``(preamble, prompts)``: ``--requests`` prompts of ``--prompt-len``
    random tokens from ``seed`` (default ``--seed``).  Common material is
    *prepended* to each prompt: a pinned preamble first (prefilled once,
    never evicted), then an optional shared prefix (cached from the first
    request that donates it)."""
    rng = np.random.default_rng(args.seed if seed is None else seed)
    preamble = (rng.integers(0, arch.vocab, (args.pin_prompt,)).astype(np.int32)
                if args.pin_prompt > 0 else None)
    common = (rng.integers(0, arch.vocab, (args.shared_prefix,)).astype(np.int32)
              if args.shared_prefix > 0 else None)
    head = [p for p in (preamble, common) if p is not None]
    prompts = [np.concatenate(head + [rng.integers(0, arch.vocab, (args.prompt_len,)).astype(np.int32)])
               if head else rng.integers(0, arch.vocab, (args.prompt_len,)).astype(np.int32)
               for _ in range(args.requests)]
    return preamble, prompts


def paged_engine(arch, params, args, *, sample=None, decode_kernel=None, obs=None,
                 preamble=None):
    """The paged (or speculative) engine the flags describe; ``sample`` and
    ``decode_kernel`` override the flags' values when given."""
    if sample is None:
        sample = SampleConfig(method=args.sample, temperature=args.temperature,
                              top_k=args.top_k)
    if decode_kernel is None:
        decode_kernel = args.decode_kernel
    kw = dict(
        batch=args.batch, max_seq=args.max_seq,
        block_size=args.block_size, prefill_chunk=args.prefill_chunk,
        num_blocks=args.num_blocks, sample=sample, seed=args.seed,
        kv_quant=args.kv_int8, kv_bits=args.kv_bits,
        prefix_share=args.prefix_share,
        eos_id=args.eos_id, decode_steps=args.decode_steps, obs=obs,
        rt=Runtime(decode_kernel=decode_kernel, int_forward=args.int_forward,
                   int_chain=args.int_chain),
    )
    if args.spec_k > 0:
        from repro.serve.spec import ModelDrafter, SpecServeEngine

        drafter = None
        if args.spec_draft != "self-int8":
            darch = get_arch(args.spec_draft)
            if args.reduced:
                darch = reduced(darch)
            if darch.vocab != arch.vocab:
                raise SystemExit(
                    f"draft config {args.spec_draft} vocab {darch.vocab} != "
                    f"target vocab {arch.vocab}"
                )
            dparams = unbox(init_lm(jax.random.PRNGKey(args.seed + 1), darch))
            drafter = ModelDrafter(
                darch, dparams, slots=args.batch, max_seq=args.max_seq,
                spec_k=args.spec_k, block_size=args.block_size,
                prefill_chunk=args.prefill_chunk,
            )
        e = SpecServeEngine(arch, params, spec_k=args.spec_k, drafter=drafter, **kw)
    else:
        e = PagedServeEngine(arch, params, **kw)
    if preamble is not None:
        pinned = e.pin_prompt(preamble)
        print(f"pinned system preamble: {pinned} of {len(preamble)} tokens "
              f"({pinned // e.cache.block_size} blocks, never evicted)")
    return e


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    arch = load_arch(args)
    params = load_params(arch, args)
    if args.deploy_int8:
        print("serving deployed int8 weights (A2Q-guaranteed accumulator safety)")
    if args.int_chain:
        print("int-chain: activation quantization folded into the W8A8 kernel "
              "(int8 codes chained between deployed layers)")
    elif args.int_forward:
        print("int-forward: deployed linears run the fused W8A8 integer kernel")

    preamble, prompts = make_prompts(arch, args)
    sample = SampleConfig(method=args.sample, temperature=args.temperature, top_k=args.top_k)
    decode_kernel = args.decode_kernel
    if args.parity_check and (args.sample != "greedy" or decode_kernel):
        # the contiguous baseline is always greedy via the gathered-view
        # arithmetic; comparing anything else would fail by construction
        print("parity-check forces greedy sampling on the jnp decode path")
        sample = SampleConfig()
        decode_kernel = False
    if args.eos_auto:
        # greedy contiguous probe: the token request 0 emits halfway through
        # its budget becomes the EOS id — greedy determinism then guarantees
        # at least that request terminates early in every engine under test
        probe = ServeEngine(arch, params, batch=args.batch, max_seq=args.max_seq)
        ptoks = probe.generate(prompts[:1], max_new=args.max_new)[0]
        args.eos_id = int(ptoks[len(ptoks) // 2])
        print(f"eos-auto: eos_id={args.eos_id} (request 0's token at step {len(ptoks) // 2})")

    obs = Obs(trace=bool(args.trace))

    report: dict = {
        "arch": args.arch, "paged": bool(args.paged or args.parity_check),
        "int_forward": args.int_forward, "int_chain": args.int_chain,
        "kv_int8": args.kv_int8,
        "kv_bits": args.kv_bits if args.kv_int8 else None,
        "spec_k": args.spec_k, "prefix_share": args.prefix_share,
        "shared_prefix": args.shared_prefix, "pin_prompt": args.pin_prompt,
        "decode_steps": args.decode_steps, "eos_id": args.eos_id,
    }
    if args.parity_check:
        # the baseline stays on the float truth path: dequant matmuls
        # (default Runtime) over the fp32 contiguous cache — so parity with
        # --int-forward/--kv-int8 gates the whole integer path against it
        contig = ServeEngine(arch, params, batch=args.batch, max_seq=args.max_seq,
                             eos_id=args.eos_id)
        reqs_c: list = []
        if contig.recurrent:
            # the contiguous baseline serves recurrent archs one lockstep
            # group (<= batch equal-length prompts) at a time
            outs_c = []
            for lo in range(0, len(prompts), args.batch):
                outs_c += contig.generate(prompts[lo:lo + args.batch], max_new=args.max_new)
                reqs_c += contig.last_requests
        else:
            outs_c = contig.generate(prompts, max_new=args.max_new)
            reqs_c = contig.last_requests
        pagede = paged_engine(arch, params, args, sample=sample,
                              decode_kernel=decode_kernel, obs=obs, preamble=preamble)
        outs_p = pagede.generate(prompts, max_new=args.max_new)
        report["contiguous"] = _report("contiguous", contig)
        report["paged_engine"] = _report("paged", pagede)
        report["kv_bytes_per_token"] = pagede.cache.kv_bytes_per_token()
        if args.prefix_share:
            print(f"prefix sharing: {pagede.cache.prefix_hits} hits, "
                  f"{pagede.cache.prefix_hit_tokens} prompt tokens served from "
                  f"shared blocks, {pagede.cache.cow_copies} CoW copies")
            report["prefix_hits"] = pagede.cache.prefix_hits
            report["prefix_hit_tokens"] = pagede.cache.prefix_hit_tokens
            report["cow_copies"] = pagede.cache.cow_copies
        if args.spec_k > 0:
            report["spec"] = _spec_report(pagede)
        if args.kv_int8:
            # int8 KV is lossy: token parity holds up to quantization ties
            # (see serve.engine.parity_up_to_ties and serve/README.md "parity bound")
            eps = PARITY_EPS if args.parity_eps is None else args.parity_eps
            ok, ties, detail = parity_up_to_ties(reqs_c, outs_p, eps)
            report["parity_eps"] = eps
            report["parity_sub_margin_ties"] = ties
            if not ok:
                raise SystemExit(f"parity FAILED (int8 KV, eps={eps}): {detail}")
            print(f"parity OK (int8 KV): {len(outs_p)} requests token-identical "
                  f"up to {ties} sub-margin ties (eps={eps})")
        else:
            if outs_c != outs_p:
                raise SystemExit(f"parity FAILED: contiguous {outs_c} != paged {outs_p}")
            print(f"parity OK: {len(outs_p)} requests token-identical across engines")
        assert report["paged_engine"]["decode_tok_s"] > 0, "no decode throughput measured"
        outs = outs_p
        engine = pagede
    elif args.paged:
        engine = paged_engine(arch, params, args, sample=sample,
                              decode_kernel=decode_kernel, obs=obs, preamble=preamble)
        outs = engine.generate(prompts, max_new=args.max_new)
        report["paged_engine"] = _report("paged", engine)
        cache = engine.cache
        print(f"paged KV: peak {cache.peak_blocks} blocks "
              f"({cache.peak_blocks * cache.block_size} tokens) of "
              f"{cache.num_blocks - 1} (block_size={cache.block_size}); "
              f"contiguous equivalent {args.batch * args.max_seq} tokens; "
              f"{cache.kv_bytes_per_token()} KV bytes/token"
              f"{' (int8 blocks)' if args.kv_int8 else ''}")
        report["paged_peak_blocks"] = cache.peak_blocks
        report["kv_bytes_per_token"] = cache.kv_bytes_per_token()
        if args.prefix_share:
            print(f"prefix sharing: {cache.prefix_hits} hits, "
                  f"{cache.prefix_hit_tokens} prompt tokens served from shared "
                  f"blocks, {cache.cow_copies} CoW copies")
            report["prefix_hits"] = cache.prefix_hits
            report["prefix_hit_tokens"] = cache.prefix_hit_tokens
            report["cow_copies"] = cache.cow_copies
        if args.spec_k > 0:
            report["spec"] = _spec_report(engine)
    else:
        # the contiguous engine honors --int-forward too (apply_lm threads it
        # through the contiguous cache path) — without this the flag would be
        # a silent no-op here while the banner claims the W8A8 kernel is on
        engine = ServeEngine(arch, params, batch=args.batch, max_seq=args.max_seq,
                             rt=Runtime(int_forward=args.int_forward,
                                        int_chain=args.int_chain),
                             eos_id=args.eos_id, obs=obs)
        outs = engine.generate(prompts, max_new=args.max_new)
        report["contiguous"] = _report("contiguous", engine)

    if args.eos_id is not None:
        report["eos_terminated"] = sum(1 for o in outs if o and o[-1] == args.eos_id)
        print(f"eos: {report['eos_terminated']} of {len(outs)} requests "
              f"terminated on eos_id={args.eos_id}")
    if args.int_forward:
        # accumulator-headroom telemetry: static L1 utilization per deployed
        # layer (the paper's Eq. 11 ratio) plus observed int accumulator
        # magnitudes sampled through an eager probed forward
        hr = engine_headroom(engine)
        report["headroom"] = hr
        print(f"acc headroom: {hr['layers']} deployed layers, "
              f"max static utilization {hr['util_max']:.4f}, "
              f"max observed |acc|/bound {hr['observed_frac_max']:.4f}, "
              f"{hr['violations']} violations")
    for i, o in enumerate(outs):
        print(f"req {i}: {o}")
    if args.trace:
        engine.obs.trace.export(args.trace)
        print(f"wrote trace ({len(engine.obs.trace.events)} events) to {args.trace}")
    if args.metrics_json:
        snap = engine.metrics_snapshot()
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
        print(f"wrote {len(snap)} metrics to {args.metrics_json}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")
    return outs


if __name__ == "__main__":
    main()
