"""Bring-up smoke run on a TPU: the A2Q serve path and A2Q training, at
published widths, through the entry points a user calls.

    python chip_smoke.py             # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4   # four chips: mesh training and its
                                     # one-chip reference, nothing else

Serve phase: yi-6b at its published config (32 layers, d_model 4096, GQA
32/4, head_dim 128, d_ff 11008, vocab 64000), random weights from
``--seed`` deployed to int8 a layer at a time, served by the engine
``repro.launch.serve`` builds for ``--paged --int-chain --decode-kernel
--kv-int8 --decode-steps 8``: 8 requests of 512 prompt tokens, 32 new tokens
each.  It checks that every request finishes, that greedy tokens match the
plain path (same engine and weights, default ``Runtime``, float KV) up to
sub-margin ties, that the int8 chain has no standalone or fallback site,
that the A2Q accumulator headroom holds, that the compiled decode megastep
contains the Pallas kernels, and that a second wave of the same shapes
compiles nothing.

Train phase: smollm-135m at its published config (30 layers), 5 A2Q steps of
batch 8 x 512 tokens through ``repro.launch.train.train``; the loss must be
finite.

``--chips 4``: yi-6b at published widths, depth cut so one chip holds the
reference, trained on the planned mesh over four chips and on one chip in
the same process; the per-step losses must agree within ``MESH_LOSS_TOL``.

Every check raises.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU, or without the repository's ``src/`` next to this file, the
script exits non-zero before running anything.  Throughputs printed here are
from a smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# the serve workload, in the launcher's own flags
SERVE_FLAGS = [
    "--arch", "yi-6b", "--paged", "--requests", "8", "--prompt-len", "512",
    "--max-new", "32", "--batch", "8", "--block-size", "16",
    "--prefill-chunk", "256", "--max-seq", "1024",
]
# the composition a deployment runs, and the plain path it is checked against
FAST_FLAGS = ["--int-chain", "--decode-kernel", "--kv-int8", "--decode-steps", "8"]
PLAIN_FLAGS = ["--deploy-int8"]

TRAIN_ARCH, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = "smollm-135m", 5, 8, 512

# --chips 4: at yi-6b widths, 2 layers with the dry-run's factored optimizer
# (adafactor) compile to ~7.1 GB on one v5e; adamw's moments would need ~13.5
MESH_DEPTH, MESH_STEPS, MESH_BATCH, MESH_SEQ = 2, 4, 8, 256
MESH_OPTIMIZER = "adafactor"
# sharded and single-chip steps reduce in different orders in bf16, and the
# A2Q quantizers round what differs; losses start near ln(64000) = 11.07
MESH_LOSS_TOL = 0.05


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _assert_kernels(text: str, what: str) -> None:
    _check("tpu_custom_call" in text, f"{what}: no Pallas kernel in the compiled program")


def _peak_memory() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "peak device memory not reported"
    return f"peak device memory so far {stats['peak_bytes_in_use'] / 1e9:.3f} GB"


def _jit_sizes(engine) -> dict:
    # compiled-program counts per jitted entry point, from the engine's own
    # jit_cache_size{fn=...} gauges
    snap = engine.metrics_snapshot()
    return {k[len("jit_cache_size{fn="):-1]: int(v["value"])
            for k, v in snap.items() if k.startswith("jit_cache_size{")}


def serve_phase(workload: list, seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import serve as cli
    from repro.obs.headroom import engine_headroom
    from repro.serve.engine import parity_up_to_ties

    base = workload + ["--seed", str(seed)]
    fast_args = cli.parse_args(base + FAST_FLAGS)
    plain_args = cli.parse_args(base + PLAIN_FLAGS)
    arch = cli.load_arch(fast_args)
    st = arch.stacks[0]
    _log(f"[serve] {arch.name}: {arch.n_layers} layers, d_model {arch.d_model}, "
         f"heads {st.attn.heads}/{st.attn.kv_heads} x {st.attn.head_dim}, "
         f"d_ff {st.d_ff}, vocab {arch.vocab}, A2Q acc_bits {arch.quant.acc_bits}")

    t0 = time.perf_counter()
    params = jax.block_until_ready(cli.load_params(arch, fast_args))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    _log(f"[serve] deployed int8 artifact: {nbytes / 1e9:.3f} GB, built in "
         f"{time.perf_counter() - t0:.1f} s (set-up); {_peak_memory()}")
    _, prompts = cli.make_prompts(arch, fast_args)
    max_new = fast_args.max_new

    fast = cli.paged_engine(arch, params, fast_args)
    t0 = time.perf_counter()
    outs = fast.generate(prompts, max_new=max_new)
    _log(f"[serve] fast path, first wave (compiles included): "
         f"{time.perf_counter() - t0:.1f} s (set-up); {_peak_memory()}")
    done = sum(r.done and len(r.generated) == max_new for r in fast.last_requests)
    _log(f"[serve] requests complete: {done}/{len(prompts)}")
    _check(done == len(prompts), "not every request finished")

    tp = fast.throughput()
    _log(f"[serve] int8 chain: {tp['int_chain_folded']} folded, "
         f"{tp['int_chain_chained']} chained, {tp['int_chain_requant_dispatches']} "
         f"standalone, {tp['int_chain_fallback']} fallback sites")
    _check(tp["int_chain_requant_dispatches"] == 0, "standalone act-quant sites")
    _check(tp["int_chain_fallback"] == 0, "int8 chain fallback sites")

    B = fast.batch
    z = np.zeros((B,), np.int32)
    t0 = time.perf_counter()
    text = fast._megadecode.lower(
        fast.params, z, fast.cache.pools, fast.cache.bt(), z, np.zeros((B,), bool),
        z, z, jax.random.PRNGKey(0),
    ).compile().as_text()
    _assert_kernels(text, "decode megastep")
    _log(f"[serve] compiled decode megastep holds {text.count('tpu_custom_call')} "
         f"tpu_custom_call sites (re-compiled in {time.perf_counter() - t0:.1f} s)")

    plain = cli.paged_engine(arch, params, plain_args)
    t0 = time.perf_counter()
    plain.generate(prompts, max_new=max_new)
    _log(f"[serve] plain path run (compiles included): {time.perf_counter() - t0:.1f} s; "
         f"{_peak_memory()}")
    ok, ties, detail = parity_up_to_ties(plain.last_requests, outs, cli.PARITY_EPS)
    same = sum(r.generated == o for r, o in zip(plain.last_requests, outs))
    _log(f"[serve] parity with the plain path: {same}/{len(outs)} requests "
         f"token-identical, {ties} sub-margin ties (eps={cli.PARITY_EPS})")
    # where each request first diverges, with the plain path's top-2 margin
    # there (a tie needs margin <= eps) and its median margin for scale
    for i, (r, o) in enumerate(zip(plain.last_requests, outs)):
        t = next((t for t, (x, y) in enumerate(zip(r.generated, o)) if x != y), None)
        if t is not None:
            _log(f"[serve]   request {i}: first divergence at step {t}, margin "
                 f"{r.margins[t]:.4f} (median {float(np.median(r.margins)):.4f})")
    _check(ok, f"parity with the plain path failed: {detail}")
    del plain

    before = _jit_sizes(fast)
    fast.reset_stats()
    _, prompts2 = cli.make_prompts(arch, fast_args, seed=seed + 1)
    t0 = time.perf_counter()
    fast.generate(prompts2, max_new=max_new)
    wall = time.perf_counter() - t0
    after = _jit_sizes(fast)
    _log(f"[serve] second wave: jit cache sizes {after} (before {before})")
    _check(after == before, "the second wave compiled")
    tp = fast.throughput()
    _log(f"[serve] second wave (smoke run, not a benchmark): {wall:.3f} s wall, "
         f"prefill {tp['prefill_tok_s']:.1f} tok/s, decode {tp['decode_tok_s']:.1f} "
         f"tok/s, {tp['dispatches_per_token']:.3f} decode dispatches/token")

    hr = engine_headroom(fast)
    _log(f"[serve] A2Q headroom: {hr['layers']} deployed layers, max static "
         f"utilization {hr['util_max']:.4f}, max observed |acc|/bound "
         f"{hr['observed_frac_max']:.4f}, {hr['violations']} violations")
    _check(hr["violations"] == 0, "accumulator headroom violations")
    _check(hr["util_max"] < 1.0, "static headroom utilization reached 1.0")
    _log(f"[serve] {_peak_memory()}")


def _losses(result) -> list:
    losses = [rec["loss"] for rec in result.history]
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    return losses


def train_phase(arch, steps: int, batch: int, seq: int, seed: int, devices) -> None:
    from repro.launch.train import train

    _log(f"[train] {arch.name}: {arch.n_layers} layers, d_model {arch.d_model}, "
         f"vocab {arch.vocab}, A2Q acc_bits {arch.quant.acc_bits}; "
         f"{steps} steps of batch {batch} x {seq}")
    result = train(arch, steps=steps, batch=batch, seq=seq, devices=devices, seed=seed)
    losses = _losses(result)
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps recorded")
    times = [rec["step_time"] for rec in result.history]
    _log(f"[train] loss first {losses[0]:.4f} last {losses[-1]:.4f}; step 0 "
         f"{times[0]:.1f} s (compile, set-up), later steps {min(times[1:]):.4f}-"
         f"{max(times[1:]):.4f} s (smoke run, not a benchmark)")


def mesh_phase(arch, steps: int, batch: int, seq: int, seed: int, devices) -> None:
    from repro.launch.train import train

    _log(f"[mesh-train] {arch.name} at published widths, depth cut to "
         f"{arch.n_layers} layers so one chip holds the reference; {steps} "
         f"{MESH_OPTIMIZER} steps of batch {batch} x {seq}")
    kw = dict(steps=steps, batch=batch, seq=seq, optimizer=MESH_OPTIMIZER, seed=seed)
    ref = _losses(train(arch, devices=devices[:1], **kw))
    _log(f"[mesh-train] one chip losses: {ref}")
    got = _losses(train(arch, devices=devices, **kw))
    _log(f"[mesh-train] {len(devices)} chips losses: {got}")
    diff = max(abs(a - b) for a, b in zip(ref, got))
    _log(f"[mesh-train] max |loss difference| {diff:.6f} (tolerance {MESH_LOSS_TOL})")
    _check(len(got) == len(ref) == steps, "missing steps")
    _check(diff <= MESH_LOSS_TOL, "mesh losses do not match the one-chip reference")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh training path and its "
                         "one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no src/repro next to {__file__}; run it "
                         "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    from repro.configs import get_arch
    from repro.launch.cache import enable_compile_cache

    _log(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
         f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        yi = get_arch("yi-6b")
        arch = dataclasses.replace(
            yi, stacks=(dataclasses.replace(yi.stacks[0], count=MESH_DEPTH),))
        mesh_phase(arch, MESH_STEPS, MESH_BATCH, MESH_SEQ, args.seed, devices[:4])
    else:
        serve_phase(SERVE_FLAGS, args.seed)
        train_phase(get_arch(TRAIN_ARCH), TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ,
                    args.seed, devices[:1])
    _log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
