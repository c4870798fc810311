"""Serving cells: the A2Q int8 model served by ``PagedServeEngine`` as
``repro.launch.serve`` builds it from the configuration's engine flags.

Traffic ``offline`` (the workload file's ``traffic`` key) is a batch job:
every slot decodes all the time, the queue always holds more requests than
there are slots.  The window reports ``output_tok_s``, every token emitted
over the window's length.

What the window produced is compared with the plain float32 reference
(``reference/llama.py``) once the window has closed and the engine is
freed: over a sample of the served requests drawn from the seed, with the
longest in it, the widest gap by which a served token's reference logit
lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import core
from bench.model import arch_from_config, make_params
from bench.traffic import requests as traffic


def _engine(ctx, arch, params):
    from repro.launch import serve as cli
    from repro.obs import Obs

    w, cfg = ctx.cell.workload, ctx.cell.config
    flags = ["--arch", cfg["name"], *cfg["engine"], *ctx.engine_flags,
             "--batch", str(w["slots"]), "--max-seq", str(w["max_seq"]),
             "--num-blocks", str(w["num_blocks"]), "--seed", str(ctx.seed % 2**31)]
    args = cli.parse_args(flags)
    return cli.paged_engine(arch, params, args, obs=Obs(trace=ctx.trace)), args


def _request(spec):
    from repro.serve.scheduler import ServeRequest

    prompt = spec.prompt if spec.context is None else np.concatenate([spec.prompt, spec.context])
    return ServeRequest(uid=spec.uid, prompt=prompt, max_new=spec.max_new)


def _warm(ctx, eng, lengths, rng) -> None:
    """Compile (or load) every program the window will run: one prefill per
    chunk shape of the cell's prompt lengths, the block-table patch for each
    count of changed rows, and the decode megastep at the cell's slot count."""
    from repro.serve.scheduler import ServeRequest

    vocab = eng.arch.vocab
    shapes = traffic.prefill_shapes(lengths, eng.sched.prefill_chunk)
    for k, n in enumerate(shapes):
        eng.submit(ServeRequest(uid=-1 - k, prompt=rng.integers(0, vocab, n).astype(np.int32),
                                max_new=1))
    while not eng.sched.idle():
        eng.step()
    # the block table is patched on the device by rows changed since the last
    # step; each count of changed rows is its own small program
    eng.cache.bt()
    for k in range(1, eng.batch + 1):
        eng.cache._bt_dirty.update(range(k))
        eng.cache.bt()
    core.log(f"warmed {len(shapes)} prefill shapes and {eng.batch} block-table patches")


def _served(reqs) -> int:
    return sum(len(r.generated) for r in reqs)


class _Tracer:
    """A profiler trace of ``trace_seconds`` of the window, started once
    ``trace_after`` seconds of it have passed (traced runs only)."""

    def __init__(self, ctx, t0):
        w = ctx.cell.workload
        self.on = ctx.trace
        self.start_at = t0 + float(w.get("trace_after", 2.0))
        self.stop_at = self.start_at + float(w.get("trace_seconds", 3.0))
        self.dir = ctx.out_dir / "trace"
        self.state = 0
        self.window = (0.0, 0.0)
        self.steps = 0

    def waiting(self) -> bool:
        """The trace has started and holds no step yet."""
        return self.state == 1 and not self.steps

    def poll(self, now: float) -> None:
        import jax

        if not self.on:
            return
        if self.state == 0 and now >= self.start_at:
            jax.profiler.start_trace(str(self.dir))
            self.window = (time.perf_counter(), 0.0)
            self.state = 1
        elif self.state == 1 and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == 1:
            self.window = (self.window[0], time.perf_counter())
            jax.profiler.stop_trace()
            self.state = 2


def _step(eng, tracer, steps: list, prompt_len: dict):
    """One engine step; in the traced part of the window, annotated for the
    profiler and recorded for ``work.py``: the slots it decoded, the tokens
    they held, and the prefill chunks it ran."""
    import jax

    if tracer.state != 1:
        return eng.step()
    before = {i: int(eng.cache.lens[i]) for i in eng.sched.live}
    n_events = len(eng.obs.trace.events)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.step"):
        n = eng.step()
    t1 = time.perf_counter()
    tracer.steps += 1
    new = eng.obs.trace.events[n_events:]
    admitted = {a["slot"]: a["prompt"] for ph, name, _, _, a in new if ph == "X" and name == "admit"}
    chunk = eng.sched.prefill_chunk
    prefill = [(min(chunk, prompt_len[a["uid"]] - a["start"]), a["start"])
               for ph, name, _, _, a in new if ph == "X" and name == "prefill_chunk"]
    ctx_tokens = sum(before.values()) + sum(admitted.values())
    live = len(before) + len(admitted)
    steps.append({"t0": t0, "t1": t1, "live": live, "ctx": ctx_tokens + live,
                  "ticks": eng.decode_steps if n else 0, "prefill": prefill})
    return n


def run(ctx) -> dict:
    import jax

    cfg, w = ctx.cell.config, ctx.cell.workload
    arch = arch_from_config(cfg)
    t = time.perf_counter()
    params = jax.block_until_ready(make_params(cfg, ctx.seed, deployed=True))
    ctx.setup["weights_s"] = time.perf_counter() - t
    eng, args = _engine(ctx, arch, params)
    warm_rng = traffic.rng_for(ctx.seed, 2)
    if w["traffic"] != "offline":
        raise ValueError(f"unknown serving traffic {w['traffic']!r}")
    initial, backlog = traffic.offline_backlog(
        {**w, "prefill_chunk": args.prefill_chunk}, ctx.seed, arch.vocab, eng.batch)
    specs = initial + backlog
    t = time.perf_counter()
    _warm(ctx, eng, [len(s.prompt) for s in specs], warm_rng)
    ctx.setup["warm_s"] = time.perf_counter() - t

    t = time.perf_counter()
    reqs = [_request(s) for s in specs]
    for r in reqs[: eng.batch]:
        eng.submit(r)
    eng.step()  # prefill of every slot's context, and the first megastep
    for r in reqs[eng.batch:]:
        eng.submit(r)
    jax.block_until_ready(eng.cache.pools)
    ctx.setup["context_s"] = time.perf_counter() - t
    eng.reset_stats()
    ctx.begin_window()

    prompt_len = {r.uid: len(r.prompt) for r in reqs}
    steps = ctx.counters.setdefault("steps", [])
    t0 = time.perf_counter()
    tracer = _Tracer(ctx, t0)
    served0 = _served(reqs)
    try:
        while True:
            now = time.perf_counter()
            tracer.poll(now)
            if now - t0 >= ctx.seconds and not tracer.waiting():
                break
            if eng.sched.idle():
                raise RuntimeError("the offline backlog ran dry inside the window; the cell needs "
                                   "a longer backlog")
            _step(eng, tracer, steps, prompt_len)
        t1 = time.perf_counter()
    finally:
        tracer.stop()
    ctx.end_window()
    emitted = _served(reqs) - served0
    e2e = {"output_tok_s": emitted / (t1 - t0)}
    attempted = sum(1 for r in reqs if r.generated)
    ctx.record.window = (t0, t1)
    ctx.record.trace_window = tracer.window
    ctx.record.spans = eng.obs.trace.spans()
    ctx.counters.update({k: v for k, v in eng.throughput().items() if isinstance(v, (int, float))})
    ctx.counters["slots"] = eng.batch
    ctx.counters["decode_steps"] = eng.decode_steps
    ctx.counters["prefill_chunk"] = eng.sched.prefill_chunk
    if tracer.window[1]:
        ctx.trace_dir = tracer.dir

    from repro.obs.headroom import static_headroom_report

    violations = sum(1 for rec in static_headroom_report(params, arch.quant)
                     if rec["utilization"] > 1.0)
    ctx.read_memory()
    # finished or not: few of an offline job's long answers end in a window
    sample = _sample(ctx, [r for r in reqs if r.generated])
    contexts = [(np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)]),
                 len(r.prompt), np.asarray(r.generated, np.int32)) for r in sample]
    del eng
    gc.collect()
    t = time.perf_counter()
    from bench.reference import llama

    gaps = np.concatenate([llama.gaps(params, cfg, *c, pad_to=w["max_seq"]) for c in contexts])
    ctx.counters["reference_s"] = time.perf_counter() - t
    numbers = {"served_logit_gap": float(np.max(gaps)), "served_logit_gap_mean": float(np.mean(gaps)),
               "served_off_best": float(np.mean(gaps > 0))}
    ctx.counters.update(numbers=numbers, checked_tokens=int(len(gaps)),
                        checked_requests=len(contexts))
    checks = [core.Check(k, numbers[k], lim) for k, lim in w["limits"].items()]
    checks += [core.Check("a2q_l1_violations", float(violations), 0.0),
               core.Check("window_compiles", float(ctx.window_compiles), 0.0)]
    return {"e2e": e2e, "checks": checks, "attempted": attempted, "failed": 0}


def _sample(ctx, reqs) -> list:
    """The requests whose served tokens are checked: the longest, then others
    drawn from the seed, until ``check_tokens`` served tokens or
    ``check_requests`` requests."""
    w = ctx.cell.workload
    longest = max(reqs, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in reqs if r is not longest]
    order = traffic.rng_for(ctx.seed, 3).permutation(len(rest))
    out = [longest]
    for i in order:
        if len(out) >= int(w["check_requests"]) or \
                sum(len(r.generated) for r in out) >= int(w["check_tokens"]):
            break
        out.append(rest[i])
    return out
