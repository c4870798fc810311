"""Serving engines: continuous batching over contiguous or paged KV caches.

Two engines share the scheduler/request machinery (``serve/scheduler.py``):

* :class:`ServeEngine` — the seed engine, kept as the measured baseline: one
  contiguous ``max_seq`` cache lane per slot, prompts prefilled one token per
  jit call, logits round-tripped to the host for argmax every tick.
* :class:`PagedServeEngine` — the serving subsystem: block-table paged KV
  memory (``serve/paged_cache.py``), chunked one-shot prefill (whole prompt
  chunks per jit call on an isolated B=1 cache view), on-device sampling
  (``serve/sampling.py``; the host only ever fetches token ids), and an
  optional Pallas paged-attention decode kernel (``Runtime(decode_kernel=
  True)``).  Continuous batching works for recurrent stacks too — per-slot
  prefill never touches other rows' states — with the scheduler's lockstep
  mode kept as the conservative equal-length-group fallback.

Deployment option ``deploy_params`` swaps trained A2Q params for int8 weights
+ per-channel scales — the artifact whose l1 norms provably fit the target
accumulator (the serving payoff of the paper's guarantee; also the
memory-roofline lever recorded in EXPERIMENTS.md SPerf).

Both engines keep ``stats`` = {prefill_tokens, decode_tokens, prefill_s,
decode_s, decode_dispatches} so launchers and benchmarks report prefill and
decode throughput separately instead of one aggregate tok/s, plus the
dispatch-count scoreboard ``dispatches_per_token`` (how many jitted decode
launches each generated token paid for — 1.0 for per-tick engines, ~1/N for
the paged megastep at ``decode_steps=N``).

Accounting convention (shared by both engines): the first generated token is
produced by the *prefill* dispatch's logits and is booked under prefill time
with zero decode tokens; ``decode_tokens`` counts only tokens whose forward
ran in a decode dispatch (``max_new - 1`` per request, absent early EOS).
The seed contiguous engine booked that first token under decode instead —
64 vs 56 decode tokens for the identical 8x8 workload — skewing every
cross-engine ``decode_tok_s`` comparison ~14%.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, QuantConfig
from repro.kernels.paged_attention import compute_block_pages, kv_block_range
from repro.models.lm import Runtime, apply_lm, init_cache, init_lm
from repro.nn.linear import deploy_linear
from repro.nn.module import unbox
from repro.nn.transformer import init_block
from repro.obs import Obs
from repro.serve.paged_cache import PagedKVCache
from repro.serve.sampling import SampleConfig, sample_tokens
from repro.serve.scheduler import Scheduler, ServeRequest

__all__ = [
    "ServeEngine", "PagedServeEngine", "Request", "deploy_params", "deploy_boxed",
    "init_deployed_lm", "parity_up_to_ties",
]


def deploy_params(params: dict, q: QuantConfig) -> dict:
    """Convert every quantized linear's (v,t,d)/(w,wq) into {q8, s8}.

    Halves weight bytes (int8 vs bf16/fp32) on the serve path; sound because
    A2Q guarantees the P-bit accumulator for the resulting integer weights.
    """

    def one(node, signed):
        # leading dims (scan layers, experts) are vmapped onto the 2D core
        lead = node["v" if "v" in node else "w"].ndim - 2
        fn = lambda sub: deploy_linear(sub, q, input_signed=signed)
        for _ in range(lead):
            fn = jax.vmap(fn)
        keys = ("v", "t", "d") if "v" in node else ("w", "wq")
        sub = {k: node[k] for k in keys if k in node}
        out = fn(sub)
        for passthrough in ("aq", "b"):
            if passthrough in node:
                out[passthrough] = node[passthrough]
        return out

    def walk(node, path=()):
        if isinstance(node, dict):
            keys = set(node.keys())
            if ("v" in keys and "t" in keys and "d" in keys) or ("w" in keys and "wq" in keys):
                signed = not (len(path) >= 2 and path[-2] == "cm" and path[-1] == "wv")
                return one(node, signed)
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return node

    return walk(params)


def init_deployed_lm(key, arch: ArchConfig) -> dict:
    """``deploy_params(unbox(init_lm(key, arch)), arch.quant)``, built so the
    float weights of a layer stack never exist whole: each stack is a
    ``lax.map`` that inits and deploys one layer per iteration (same
    per-layer keys as ``init_stack``), and the whole build is one jitted
    program, so the compiler bounds what is live at once — the int8 stacks
    plus one layer's float weights, or the float embedding and head.  A
    published-width model whose fp32 weights exceed the device (yi-6b:
    ~24 GB) deploys this way into its ~7 GB int8 artifact."""

    def stack_init(k, arch, s):
        def one(layer_key):
            return deploy_params(unbox(init_block(layer_key, arch, s)), arch.quant)

        return jax.lax.map(one, jax.random.split(k, s.count))

    def build(k):
        return deploy_params(unbox(init_lm(k, arch, stack_init=stack_init)), arch.quant)

    return jax.jit(build)(key)


def deploy_boxed(boxed_tree, q: QuantConfig):
    """Shape-level twin of :func:`deploy_params` for the dry-run: transforms a
    *boxed ShapeDtypeStruct* tree so the serve graph can be lowered against
    int8 weight storage without materializing anything.  q8 inherits the
    weight's logical axes, s8 the per-channel axes."""
    import jax

    from repro.nn.module import Boxed

    def walk(node):
        if isinstance(node, dict):
            keys = set(node.keys())
            if "v" in keys and "t" in keys and "d" in keys:
                v, t = node["v"], node["t"]
                out = {
                    "q8": Boxed(jax.ShapeDtypeStruct(v.value.shape, jnp.int8), v.axes),
                    "s8": Boxed(jax.ShapeDtypeStruct(t.value.shape, jnp.float32), t.axes),
                }
                for passthrough in ("aq", "b"):
                    if passthrough in node:
                        out[passthrough] = node[passthrough]
                return out
            if "w" in keys and "wq" in keys:
                w = node["w"]
                out = {
                    "q8": Boxed(jax.ShapeDtypeStruct(w.value.shape, jnp.int8), w.axes),
                    "s8": Boxed(
                        jax.ShapeDtypeStruct(w.value.shape[-1:], jnp.float32),
                        (w.axes[-1],),
                    ),
                }
                for passthrough in ("aq", "b"):
                    if passthrough in node:
                        out[passthrough] = node[passthrough]
                return out
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(boxed_tree)



def parity_up_to_ties(ref_reqs, outs_test, eps: float):
    """Token-parity bound for lossy (int8-KV) serving: compare each request's
    generated prefix against the float reference and fail on any mismatch at
    a step where the reference's greedy top-2 logit margin exceeds ``eps``.
    A mismatch *below* the margin is a quantization-noise tie — the int8 path
    was within its error budget of the float decision — and the prefixes
    legitimately diverge from there, so comparison for that request stops.
    With ``eps == 0`` this is exact token parity.

    ``ref_reqs`` are the reference engine's driven :class:`ServeRequest`
    objects (``engine.last_requests``) — tokens and margins index-aligned.
    Returns ``(ok, n_ties, detail)``.  Documented in serve/README.md
    ("parity bound"); gated by launch/serve --parity-check --kv-int8,
    benchmarks/serve_bench.py, and tests/test_paged.py.
    """
    ties = 0
    for r, req in enumerate(ref_reqs):
        for t, (x, y) in enumerate(zip(req.generated, outs_test[r])):
            if x != y:
                if req.margins[t] > eps:
                    return False, ties, (
                        f"req {r} step {t}: {x} != {y} with reference margin "
                        f"{req.margins[t]:.4f} > eps {eps}"
                    )
                ties += 1
                break
    return True, ties, None


# Back-compat alias: the seed engine's request type is the scheduler's.
Request = ServeRequest


def _normalize_prompt(prompt, bos_id: int) -> np.ndarray:
    """Empty prompts synthesize a BOS token: the model needs at least one
    position of context before it can emit logits (the seed engine raised a
    ``NameError`` here — ``logits`` unbound when the prefill loop never ran)."""
    arr = np.asarray(prompt, np.int32).reshape(-1)
    if arr.size == 0:
        arr = np.asarray([bos_id], np.int32)
    return arr


def _fresh_stats() -> dict:
    return {
        "prefill_tokens": 0, "decode_tokens": 0, "prefill_s": 0.0, "decode_s": 0.0,
        "decode_dispatches": 0,
    }


class _StatsMixin:
    def reset_stats(self) -> None:
        """Zero the throughput counters (benchmarks call this after a warmup
        pass so compile time stays out of steady-state numbers).  This is the
        *one* reset path: engine stats, collected spans, live metrics, and —
        via the paged subclass — cache counters all clear together, so a
        benchmark phase can never leak counters into the next one."""
        self.stats = _fresh_stats()
        self.obs.reset()

    def throughput(self) -> dict:
        """Derived tok/s split — the one place the stats contract turns into
        reportable numbers (launcher and benchmark both consume this)."""
        st = self.stats
        total_s = st["prefill_s"] + st["decode_s"]
        total_tok = st["prefill_tokens"] + st["decode_tokens"]
        out = {
            **st,
            "prefill_tok_s": st["prefill_tokens"] / st["prefill_s"] if st["prefill_s"] > 0 else 0.0,
            "decode_tok_s": st["decode_tokens"] / st["decode_s"] if st["decode_s"] > 0 else 0.0,
            "tok_s": total_tok / total_s if total_s > 0 else 0.0,
            "dispatches_per_token": (
                st["decode_dispatches"] / st["decode_tokens"] if st["decode_tokens"] > 0 else 0.0
            ),
        }
        rt = getattr(self, "rt", None)
        if rt is not None and getattr(rt, "int_forward", False):
            # Trace-time chain report from the last compiled forward: counts of
            # apply_linear call sites by disposition.  Under --int-chain the
            # stats contract requires zero standalone act-quant dispatches.
            rep = getattr(rt, "chain_report", {}) or {}
            out["int_chain_requant_dispatches"] = len(rep.get("standalone", ()))
            out["int_chain_folded"] = len(rep.get("folded", ()))
            out["int_chain_chained"] = len(rep.get("chained", ()))
            out["int_chain_fallback"] = len(rep.get("fallback", ()))
        return out

    # -- unified metrics contract -------------------------------------------

    def _jit_sites(self) -> dict:
        """Named jitted entry points whose compile counts the registry tracks
        (the PR 6 TTFT cliff was an unobserved per-shape recompile — the
        ``jit_cache_size{fn=...}`` gauges make that class of bug a metric)."""
        return {}

    def _sync_metrics(self) -> None:
        """Fold the engine's scattered runtime state — stats dict, derived
        throughput, chain report, jit compile counts — into the registry.
        Called lazily at snapshot time: nothing on the dispatch hot path ever
        touches a metric object (per-request histograms are recorded at
        completion, everything else is state the engine already keeps)."""
        m = self.obs.metrics
        tp = self.throughput()
        for k in ("prefill_tokens", "decode_tokens", "decode_dispatches",
                  "prefill_s", "decode_s"):
            m.counter(f"serve_{k}").set(tp[k])
        for k in ("prefill_tok_s", "decode_tok_s", "tok_s", "dispatches_per_token"):
            m.gauge(f"serve_{k}").set(tp[k])
        for k in ("int_chain_requant_dispatches", "int_chain_folded",
                  "int_chain_chained", "int_chain_fallback"):
            if k in tp:
                m.gauge(k).set(tp[k])
        for name, fn in self._jit_sites().items():
            try:
                m.gauge("jit_cache_size", {"fn": name}).set(fn._cache_size())
            except Exception:
                pass  # private jax API: degrade to "no compile-count gauge"

    def metrics_snapshot(self) -> dict:
        """The one ``snapshot()`` contract: sync engine state into the
        registry, return the JSON-able view.  Consumed by ``--metrics-json``,
        serve_bench, run.py, and the cluster stats event."""
        self._sync_metrics()
        return self.obs.metrics.snapshot()


class ServeEngine(_StatsMixin):
    """Contiguous-cache baseline: per-token prefill + host-side argmax."""

    def __init__(
        self,
        arch: ArchConfig,
        params: dict,
        *,
        batch: int = 4,
        max_seq: int = 512,
        rt: Optional[Runtime] = None,
        greedy: bool = True,
        bos_id: int = 0,
        eos_id: Optional[int] = None,
        obs: Optional[Obs] = None,
    ):
        self.arch = arch
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.rt = rt or Runtime()
        self.obs = obs or Obs()
        self.greedy = greedy
        self.bos_id = bos_id
        self.eos_id = eos_id  # default for requests that don't set their own
        self.cache = init_cache(arch, batch, max_seq, dtype=jnp.dtype(arch.compute_dtype))
        self.pos = np.zeros((batch,), np.int32)  # per-slot next position
        self.slots: list[Optional[Request]] = [None] * batch
        # Recurrent mixers (rwkv6/hymba) advance a non-positional state for
        # every row on every call, so slot-at-a-time prefill would pollute
        # other live rows irreversibly.  Those archs run in synchronized-batch
        # mode: equal-length prompt groups prefilled in lockstep.  (The paged
        # engine lifts this: its prefill runs on an isolated B=1 cache view.)
        self.recurrent = any(s.kind in ("rwkv6", "hymba") for s in arch.stacks)
        self.stats = _fresh_stats()
        self._decode = jax.jit(self._decode_fn)

    def _jit_sites(self) -> dict:
        return {"decode": self._decode}

    # Prefill is implemented as sequential cached steps over the prompt so the
    # slot-granular cache stays consistent under continuous batching (a
    # batch-wide one-shot prefill would clobber other live slots).  The paged
    # engine's chunked prefill replaces this with whole-chunk jit calls.
    def _decode_fn(self, params, tokens, cache, pos):
        logits, new_cache, _ = apply_lm(
            self.params_struct(params), self.arch, tokens=tokens, cache=cache,
            start_pos=pos, rt=self.rt,
        )
        return logits, new_cache

    def params_struct(self, params):
        return params

    def admit(self, req: Request) -> bool:
        req.prompt = _normalize_prompt(req.prompt, self.bos_id)
        if req.eos_id is None:
            req.eos_id = self.eos_id
        self.obs.trace.instant("submit", {"uid": req.uid, "prompt": len(req.prompt)})
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = req
                with self.obs.trace.span("admit", {"uid": req.uid, "slot": i}):
                    self._prefill_slot(i, req)
                return True
        return False

    def _emit_token(self, slot: int, req: Request, logits_row: np.ndarray) -> bool:
        """Host-side argmax + bookkeeping for one fresh token; returns True
        (and frees the slot) when the request just completed — ``max_new``
        reached or the token *is* the request's ``eos_id`` (the seed engine
        never checked EOS and decoded garbage to the length cap)."""
        nxt = int(np.argmax(logits_row))
        top2 = np.partition(logits_row.astype(np.float32), -2)[-2:]
        req.margins.append(float(top2[1] - top2[0]))
        if not req.generated:
            req.first_token_at = time.perf_counter()
        req.generated.append(nxt)
        req.last_token = nxt
        if len(req.generated) >= req.max_new or (req.eos_id is not None and nxt == req.eos_id):
            req.done = True
            req.finished_at = time.perf_counter()
            self.slots[slot] = None
            m = self.obs.metrics
            m.counter("requests_completed").inc()
            if req.submitted_at is not None:
                m.histogram("request_latency_s").observe(req.latency)
                if req.first_token_at is not None:
                    m.histogram("request_ttft_s").observe(req.ttft)
            self.obs.trace.instant("emit", {"uid": req.uid, "tokens": len(req.generated)})
            return True
        return False

    def _prefill_slot(self, slot: int, req: Request):
        # Feed prompt tokens one at a time into this slot's cache lane.  Other
        # rows receive transient garbage at their *current* position, which
        # their own next real token overwrites before it is ever attended.
        # The final prompt step's logits yield the first generated token here,
        # booked under prefill — same convention as the paged engine (the seed
        # engine deferred it to the first tick and booked it under decode,
        # skewing decode_tok_s comparisons ~14%).
        t0 = time.perf_counter()
        with self.obs.trace.span("prefill_slot", {"uid": req.uid, "tokens": len(req.prompt)}):
            self.pos[slot] = 0
            for t in req.prompt:
                tok = np.zeros((self.batch, 1), np.int32)
                tok[slot, 0] = t
                logits, self.cache = self._decode(
                    self.params, jnp.asarray(tok), self.cache, jnp.asarray(self.pos.copy())
                )
                self.pos[slot] += 1
            last = np.asarray(jax.device_get(logits[slot, 0]))
        self.stats["prefill_s"] += time.perf_counter() - t0
        self.stats["prefill_tokens"] += len(req.prompt)
        self._emit_token(slot, req, last)

    def tick(self) -> int:
        """Advance every live slot one token; returns number of live slots.

        Slots advance at *their own* positions (per-row cache writes), so
        sequences admitted at different times interleave correctly.  Each tick
        feeds the previous token (``req.last_token``) and samples from the
        fresh logits it produces — one forward per emitted token, none wasted
        (the seed engine ran a final forward whose logits were never used).
        """
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return 0
        t0 = time.perf_counter()
        with self.obs.trace.span("decode_tick", {"live": len(live)}):
            tok = np.zeros((self.batch, 1), np.int32)
            for i in live:
                tok[i, 0] = self.slots[i].last_token
            logits, self.cache = self._decode(self.params, jnp.asarray(tok), self.cache, jnp.asarray(self.pos.copy()))
            ln = np.asarray(jax.device_get(logits[:, 0]))
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_tokens"] += len(live)
        self.stats["decode_dispatches"] += 1
        for i in live:
            req = self.slots[i]
            self.pos[i] += 1
            self._emit_token(i, req, ln[i])
        return len(live)

    def generate(self, prompts: list, max_new: int = 16) -> list[list[int]]:
        """Convenience batch API: admit all, tick until drained."""
        reqs = [
            Request(uid=i, prompt=_normalize_prompt(p, self.bos_id), max_new=max_new,
                    submitted_at=time.perf_counter())
            for i, p in enumerate(prompts)
        ]
        self.last_requests = reqs  # parity gates read tokens + margins here
        if self.recurrent:
            return self._generate_lockstep(reqs)
        pending = list(reqs)
        while pending or any(s is not None for s in self.slots):
            while pending and self.admit(pending[0]):
                pending.pop(0)
            if self.tick() == 0 and not pending:
                break
        return [r.generated for r in reqs]

    def _generate_lockstep(self, reqs: list) -> list[list[int]]:
        assert len(reqs) <= self.batch, "lockstep mode serves one group at a time"
        self.last_requests = reqs
        for r in reqs:  # admit() is bypassed here — apply the engine default
            if r.eos_id is None:
                r.eos_id = self.eos_id
        lens = {len(r.prompt) for r in reqs}
        assert len(lens) == 1, "recurrent archs require equal-length prompt groups"
        T = lens.pop()
        t0 = time.perf_counter()
        # groups start from an empty engine: drop whatever recurrent S/shift
        # (and ring kpos) the previous group's drain left in the cache
        self.cache = init_cache(self.arch, self.batch, self.max_seq,
                                dtype=jnp.dtype(self.arch.compute_dtype))
        self.pos[:] = 0
        for i, r in enumerate(reqs):
            self.slots[i] = r
        for t in range(T):
            tok = np.zeros((self.batch, 1), np.int32)
            for i, r in enumerate(reqs):
                tok[i, 0] = r.prompt[t]
            logits, self.cache = self._decode(
                self.params, jnp.asarray(tok), self.cache, jnp.asarray(self.pos.copy())
            )
            self.pos[: len(reqs)] += 1
        ln = np.asarray(jax.device_get(logits[:, 0]))
        self.stats["prefill_s"] += time.perf_counter() - t0
        self.stats["prefill_tokens"] += T * len(reqs)
        for i, r in enumerate(reqs):
            self._emit_token(i, r, ln[i])
        while any(s is not None for s in self.slots):
            self.tick()
        return [r.generated for r in reqs]


class PagedServeEngine(_StatsMixin):
    """Paged-KV serving engine: scheduler-driven continuous batching, chunked
    prefill on isolated cache views, on-device sampling.

    ``num_blocks`` bounds KV memory (default: worst case, every slot at
    ``max_seq``); admission stalls — never crashes — when blocks run out,
    resuming as finished sequences release theirs.

    ``kv_quant=True`` stores seq-indexed K/V as integer blocks with per-slot
    fp32 scales (``serve/paged_cache.py``): ~4x less KV HBM per live token
    and ~4x less decode read bandwidth at ``kv_bits=8`` (int8 codes; ~6-7x
    at ``kv_bits=4``, two packed codes per byte), at a bounded quantization
    error the parity gates bound to greedy-token agreement on reduced archs.

    ``prefix_share=True`` dedups common prompt prefixes across requests via
    the cache's prefix registry: admission adopts the longest registered
    matching block run (refcounted, copy-on-write on any later write into a
    shared block) and prefill skips the adopted tokens entirely.  Only
    fully paged archs participate (the registry refuses otherwise).
    """

    def __init__(
        self,
        arch: ArchConfig,
        params: dict,
        *,
        batch: int = 4,
        max_seq: int = 512,
        block_size: int = 16,
        prefill_chunk: int = 32,
        num_blocks: Optional[int] = None,
        rt: Optional[Runtime] = None,
        sample: Optional[SampleConfig] = None,
        lockstep: Optional[bool] = None,
        kv_quant: bool = False,
        kv_bits: int = 8,
        prefix_share: bool = False,
        bos_id: int = 0,
        eos_id: Optional[int] = None,
        decode_steps: int = 1,
        seed: int = 0,
        obs: Optional[Obs] = None,
    ):
        if decode_steps < 1:
            raise ValueError(f"decode_steps must be >= 1, got {decode_steps}")
        self.arch = arch
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.rt = rt or Runtime()
        self.obs = obs or Obs()
        self.sample_cfg = sample or SampleConfig()
        self.bos_id = bos_id
        self.eos_id = eos_id  # default for requests that don't set their own
        self.decode_steps = int(decode_steps)
        self.recurrent = any(s.kind in ("rwkv6", "hymba") for s in arch.stacks)
        self.cache = PagedKVCache(
            arch, batch, block_size=block_size, num_blocks=num_blocks,
            max_seq=max_seq, dtype=jnp.dtype(arch.compute_dtype), kv_quant=kv_quant,
            kv_bits=kv_bits,
        )
        self.prefix_share = prefix_share and self.cache.fully_paged
        self.sched = Scheduler(
            batch, prefill_chunk=prefill_chunk,
            lockstep=bool(lockstep) if lockstep is not None else False,
            obs=self.obs,
        )
        self._key = jax.random.PRNGKey(seed)
        self.stats = _fresh_stats()
        # disaggregation: uid -> exported-KV payload awaiting adoption
        # (submit_handoff queues the request; _admit consumes the payload)
        self._handoffs: dict = {}
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(2,))
        self._decode = jax.jit(self._decode_fn, donate_argnums=(2,))
        self._megadecode = jax.jit(self._megastep_fn, donate_argnums=(2,))
        self._kv_walk = self._decode_kernel_walk() if self.rt.decode_kernel else None

    def _decode_kernel_walk(self) -> Optional[tuple]:
        """``(tokens a compute block, grid blocks)`` of the paged decode
        kernel over this cache's GQA pools, or None where no stack pages
        GQA K/V (the kernel then never runs)."""
        for c in self.cache.pools.values():
            kp = c.get("attn", {}).get("kp")
            if kp is not None:
                _, _, bs, kv, width = kp.shape
                mb = self.cache.max_blocks_per_seq
                pages = compute_block_pages(bs, kv, width, kp.dtype, mb, "kps" in c["attn"])
                return pages * bs, self.batch * -(-mb // pages)
        return None

    def params_struct(self, params):
        return params

    def reset_stats(self) -> None:
        super().reset_stats()
        self.cache.reset_counters()

    def _jit_sites(self) -> dict:
        return {
            "prefill": self._prefill,
            "decode": self._decode,
            "megadecode": self._megadecode,
        }

    def _sync_metrics(self) -> None:
        super()._sync_metrics()
        m = self.obs.metrics
        cc = self.cache.counters()
        # peak_blocks is a watermark (fleet merge takes the max); the rest
        # are monotone event counts
        m.gauge("kv_peak_blocks").set(cc.pop("peak_blocks"))
        for k, v in cc.items():
            m.counter(f"kv_{k}").set(v)
        m.gauge("kv_free_blocks").set(self.cache.free_blocks)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- jitted steps (sampling fused: only token ids — plus one fp32 greedy
    # margin per row, read by the int8-KV parity bound — leave the device) --

    @staticmethod
    def _greedy_margin(logits):
        top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
        return top2[:, 0] - top2[:, 1]

    def _prefill_fn(self, params, tokens, pools, bt, start, key):
        cache = {**pools, "_paged": {"bt": bt}}
        logits, new_cache, _ = apply_lm(
            self.params_struct(params), self.arch, tokens=tokens, cache=cache,
            start_pos=start, rt=self.rt,
        )
        tok = sample_tokens(logits[:, -1], self.sample_cfg, key)
        return tok, self._greedy_margin(logits[:, -1]), new_cache

    def _decode_fn(self, params, tokens, pools, bt, pos, key):
        cache = {**pools, "_paged": {"bt": bt}}
        logits, new_cache, _ = apply_lm(
            self.params_struct(params), self.arch, tokens=tokens, cache=cache,
            start_pos=pos, rt=self.rt,
        )
        tok = sample_tokens(logits[:, 0], self.sample_cfg, key)
        return tok, self._greedy_margin(logits[:, 0]), new_cache

    def _megastep_fn(self, params, tok0, pools, bt, lens, active, rem, eos, key):
        """``decode_steps`` decode ticks fused into one jitted ``lax.scan``
        dispatch (the spec drafter's k-steps-in-one-scan shape, promoted to
        the main decode loop).  All bookkeeping the per-tick path does on the
        host runs on device instead:

        * position advance — the carry holds per-row ``pos``; each row's
          sampled token feeds the next tick's forward without a host
          round-trip;
        * finish masking — a row goes inactive the tick it emits its
          ``eos`` id (``-1`` = no EOS for that row) or exhausts ``rem``
          (remaining ``max_new`` budget), exactly mirroring
          ``Scheduler.record_token``.  Inactive rows coast: their block
          table is swapped for the all-trash-block-0 table, so their
          (garbage) KV writes land in the trash block and their real cache
          is never touched.  Per-slot non-pool leaves (ring kpos, recurrent
          S/shift) do keep advancing for coasting rows — harmless, because
          ``reset_slot`` zeroes them on the slot's next admission.

        Returns ``(B, N)`` token ids / greedy margins / emitted flags plus
        the advanced pools — one ``device_get`` per window instead of per
        token.  ``emitted[i, j]`` is True iff row i was active entering tick
        j; the host replays exactly those flags through ``record_token``, so
        greedy output is token-identical to the per-tick path.
        """
        trash_bt = jnp.zeros_like(bt)
        keys = jax.random.split(key, self.decode_steps)

        def step(carry, k):
            tok, pos, act, remaining, pools = carry
            bte = jnp.where(act[:, None], bt, trash_bt)
            cache = {**pools, "_paged": {"bt": bte}}
            logits, new_cache, _ = apply_lm(
                self.params_struct(params), self.arch, tokens=tok[:, None],
                cache=cache, start_pos=pos, rt=self.rt,
            )
            with jax.named_scope("sample"):
                nxt = sample_tokens(logits[:, 0], self.sample_cfg, k)
                marg = self._greedy_margin(logits[:, 0])
            emitted = act
            adv = act.astype(jnp.int32)
            pos2 = pos + adv
            rem2 = remaining - adv
            act2 = act & (nxt != eos) & (rem2 > 0)
            return (nxt, pos2, act2, rem2, new_cache), (nxt, marg, emitted)

        (_, _, _, _, pools), (toks, margs, emitted) = jax.lax.scan(
            step, (tok0, lens, active, rem, pools), keys
        )
        # scan stacks along the leading (tick) axis; report (B, N)
        return (
            jnp.swapaxes(toks, 0, 1), jnp.swapaxes(margs, 0, 1),
            jnp.swapaxes(emitted, 0, 1), pools,
        )

    # -- request lifecycle --------------------------------------------------

    def _slot_tokens(self, req: Request) -> int:
        """Worst-case cache positions a request may write (subclasses add
        headroom — e.g. the speculative engine's rejected-draft span)."""
        return len(req.prompt) + req.max_new

    def _release_slot(self, slot: int) -> None:
        """Finished-request teardown (subclasses add drafter state)."""
        self.cache.release(slot)

    def _on_admitted(self, slot: int, req: Request) -> None:
        """Post-prefill hook for subclasses (drafter admission)."""

    def submit(self, req: Request) -> None:
        req.prompt = _normalize_prompt(req.prompt, self.bos_id)
        if req.eos_id is None:
            req.eos_id = self.eos_id
        total = self._slot_tokens(req)
        if total > self.max_seq:
            raise ValueError(f"request needs {total} positions > max_seq={self.max_seq}")
        if self.cache.blocks_needed(total) > self.cache.num_blocks - 1:
            raise ValueError("request exceeds the paged cache's total block budget")
        self.sched.submit(req)

    # -- prefill/decode disaggregation --------------------------------------

    def can_prefill_handoff(self, req: Request) -> bool:
        """Capacity probe for a prefill-role replica: a borrowed slot and
        enough blocks for the *prompt only* (decode headroom is the decode
        replica's budget)."""
        return (
            any(r is None for r in self.sched.slots)
            and self.cache.blocks_needed(len(req.prompt))
            <= self.cache.free_blocks + self.cache.reclaimable_blocks()
        )

    def prefill_handoff(self, req: Request) -> dict:
        """Prefill-role entry point of the disaggregated cluster: run the
        prompt through the isolated chunked prefill on a borrowed free slot,
        export the written KV blocks at wire width, release the slot, and
        return the migration payload — the request never enters this
        engine's decode loop.  The payload carries the prefill's sampled
        first token (and its greedy margin), so the decode replica adopts
        at exactly the state a local admission would have produced:

            {"kv": <export_blocks payload>, "first_token": int, "margin": float}
        """
        req.prompt = _normalize_prompt(req.prompt, self.bos_id)
        if req.eos_id is None:
            req.eos_id = self.eos_id
        if len(req.prompt) > self.max_seq:
            raise ValueError(f"prompt of {len(req.prompt)} tokens > max_seq={self.max_seq}")
        free = [i for i, r in enumerate(self.sched.slots) if r is None]
        if not free:
            raise RuntimeError("prefill_handoff needs a free slot")
        slot = free[0]
        self.cache.reset_slot(slot)
        self.cache.allocate(slot, len(req.prompt))
        self.sched.slots[slot] = req  # prefill_plan reads the slot binding
        try:
            t0 = time.perf_counter()
            tok = marg = None
            with self.obs.trace.span("prefill_handoff", {"uid": req.uid}):
                for chunk, start in self.sched.prefill_plan(slot):
                    with self.obs.trace.span("prefill_chunk", {"uid": req.uid, "start": start}):
                        self.cache.ensure_writable(slot, start, start + len(chunk))
                        sub = self.cache.slice_slot(slot)
                        tok, marg, new_pools = self._prefill(
                            self.params, jnp.asarray(chunk[None, :]), sub,
                            self.cache.bt_row(slot), jnp.int32(start), self._next_key(),
                        )
                        self.cache.merge_slot(slot, new_pools)
                self.cache.lens[slot] = len(req.prompt)
                tok_h, marg_h = jax.device_get((tok, marg))
                self.stats["prefill_s"] += time.perf_counter() - t0
                self.stats["prefill_tokens"] += len(req.prompt)
                with self.obs.trace.span("kv_export", {"uid": req.uid}):
                    payload = {
                        "kv": self.cache.export_blocks(slot),
                        "first_token": int(tok_h[0]),
                        "margin": float(marg_h[0]),
                    }
        finally:
            self.sched.slots[slot] = None
            req.prefilled = 0  # a requeued copy must be able to re-prefill
            self.cache.release(slot)
        return payload

    def submit_handoff(self, req: Request, payload: dict) -> None:
        """Decode-role entry point: queue a request whose prompt KV arrives
        as a migrated block payload from a prefill replica.  Admission goes
        through the normal scheduler/block gate (the full prompt + max_new
        reservation), but ``_admit`` imports the payload's blocks instead of
        recomputing the prompt — zero prefill dispatches, decode resumes at
        ``len(prompt)`` with the handed-off first token already recorded."""
        req.prompt = _normalize_prompt(req.prompt, self.bos_id)
        if req.eos_id is None:
            req.eos_id = self.eos_id
        kv = payload["kv"]
        if kv["tokens"] != len(req.prompt):
            raise ValueError(
                f"handoff payload covers {kv['tokens']} tokens, "
                f"prompt has {len(req.prompt)}"
            )
        # fail at the queue boundary, not inside a later _admit: geometry
        # skew means the fleets were launched with mismatched cache configs
        if kv["block_size"] != self.cache.block_size:
            raise ValueError(
                f"handoff block_size {kv['block_size']} != {self.cache.block_size}"
            )
        if kv["kv_quant"] != self.cache.kv_quant or (
            kv["kv_quant"] and kv["kv_bits"] != self.cache.kv_bits
        ):
            raise ValueError(
                f"handoff kv_quant/kv_bits ({kv['kv_quant']}, {kv['kv_bits']}) do "
                f"not match this cache ({self.cache.kv_quant}, {self.cache.kv_bits})"
            )
        total = self._slot_tokens(req)
        if total > self.max_seq:
            raise ValueError(f"request needs {total} positions > max_seq={self.max_seq}")
        if self.cache.blocks_needed(total) > self.cache.num_blocks - 1:
            raise ValueError("request exceeds the paged cache's total block budget")
        self._handoffs[req.uid] = payload
        self.sched.submit(req)

    def _admit_handoff(self, slot: int, req: Request, payload: dict) -> None:
        """Adopt migrated prompt KV into a fresh slot: import the wire
        blocks, grow the allocation to the full decode reservation, and
        record the prefill replica's first token.  No prompt forward runs
        here — ``prefill_tokens`` counts zero recomputed tokens, mirroring
        the prefix-adoption accounting."""
        self.cache.reset_slot(slot)
        t0 = time.perf_counter()
        with self.obs.trace.span("kv_import", {"uid": req.uid, "slot": slot}):
            self.cache.import_blocks(slot, payload["kv"])
            self.cache.allocate(slot, self._slot_tokens(req))
        req.prefilled = len(req.prompt)
        req.margins.append(float(payload["margin"]))
        if self.prefix_share:
            self.cache.register_prefix(slot, req.prompt)
        self.stats["prefill_s"] += time.perf_counter() - t0
        self._on_admitted(slot, req)
        if self.sched.record_token(slot, int(payload["first_token"])):
            self._release_slot(slot)

    def _admission_gate(self):
        """Round-local block budget: each admitted request reserves its
        worst-case blocks against the same free pool, so a round can never
        jointly over-commit what ``allocate`` will actually hand out (two
        requests that fit individually but not together must stall the
        second, not crash it).  Prefix adoption only ever *reduces* a
        request's fresh-block draw (a copy-on-write fault consumes a block
        the sequence would otherwise have allocated outright), so the
        worst-case reservation stays sound with sharing on.  Cache-pinned
        prefix blocks count as capacity: ``allocate`` reclaims them
        (LRU/cost eviction; permanently pinned chains excluded) before it
        ever fails."""
        budget = self.cache.free_blocks + self.cache.reclaimable_blocks()

        def can_admit(req: Request) -> bool:
            nonlocal budget
            need = self.cache.blocks_needed(self._slot_tokens(req))
            if need > budget:
                return False
            budget -= need
            return True

        return can_admit

    def _admit(self, slot: int, req: Request) -> None:
        """Isolated chunked prefill: whole prompt chunks through a B=1 cache
        view of this slot — other live rows' caches and recurrent states are
        never touched, so admission composes with continuous batching on
        every arch (incl. recurrent stacks).  With ``prefix_share`` the
        longest cached prompt prefix is adopted from the radix prompt cache
        first and prefill resumes after it — at the *chunk-aligned* offset
        below the shared length, not at the shared length itself.  Resuming
        at an arbitrary offset mints a fresh XLA compile per distinct
        shared-prefix length (the chunk token array takes a new shape); the
        aligned resume keeps every chunk shape inside the fixed
        ``{prefill_chunk, len % prefill_chunk}`` set plain prefill already
        compiles.  Adoption is trimmed to the blocks covering ``[0,
        resume)``: the span ``[resume, shared)`` gets recomputed regardless
        (re-deriving bit-identical K/V — deterministic B=1 chunked prefill,
        same path the donor ran), so adopting its partial block would only
        buy a copy-on-write fault; when ``block_size`` divides
        ``prefill_chunk`` the trimmed run is all-full blocks the adopter
        never writes, and admission costs zero CoW dispatches."""
        payload = self._handoffs.pop(req.uid, None)
        if payload is not None:
            return self._admit_handoff(slot, req, payload)
        tr = self.obs.trace
        with tr.span("admit", {"uid": req.uid, "slot": slot, "prompt": len(req.prompt)}):
            self.cache.reset_slot(slot)
            adopted = 0
            if self.prefix_share:
                with tr.span("radix_lookup", {"uid": req.uid}):
                    shared, blocks = self.cache.lookup_prefix(req.prompt)
                resume = (shared // self.sched.prefill_chunk) * self.sched.prefill_chunk
                if resume > 0:
                    blocks = blocks[: self.cache.blocks_needed(resume)]
                    self.cache.adopt_prefix(slot, resume, blocks)
                    req.prefilled = adopted = resume
            with tr.span("block_alloc", {"uid": req.uid}):
                self.cache.allocate(slot, self._slot_tokens(req))
            t0 = time.perf_counter()
            tok = marg = None
            for chunk, start in self.sched.prefill_plan(slot):
                with tr.span("prefill_chunk", {"uid": req.uid, "start": start}):
                    with tr.span("cow_preflight", {"uid": req.uid}):
                        self.cache.ensure_writable(slot, start, start + len(chunk))
                    sub = self.cache.slice_slot(slot)
                    tok, marg, new_pools = self._prefill(
                        self.params, jnp.asarray(chunk[None, :]), sub,
                        self.cache.bt_row(slot), jnp.int32(start), self._next_key(),
                    )
                    self.cache.merge_slot(slot, new_pools)
            self.cache.lens[slot] = len(req.prompt)
            if self.prefix_share:
                self.cache.register_prefix(slot, req.prompt)
            tok_h, marg_h = jax.device_get((tok, marg))
            first = int(tok_h[0])
            req.margins.append(float(marg_h[0]))
            self.stats["prefill_s"] += time.perf_counter() - t0
            # adopted tokens were never recomputed — throughput counts real work
            self.stats["prefill_tokens"] += len(req.prompt) - adopted
            self._on_admitted(slot, req)
        if self.sched.record_token(slot, first):
            self._release_slot(slot)

    def _admit_group(self, group: list) -> None:
        """Lockstep fallback: equal-length group prefilled together in one
        batched chunked pass (all rows share every position)."""
        L = len(group[0][1].prompt)
        assert all(len(r.prompt) == L for _, r in group), "lockstep needs equal lengths"
        toks = np.zeros((self.batch, L), np.int32)
        for slot, req in group:
            self.cache.reset_slot(slot)
            self.cache.allocate(slot, L + req.max_new)
            toks[slot] = req.prompt
            req.prefilled = L
        t0 = time.perf_counter()
        tok = marg = None
        with self.obs.trace.span("admit_group", {"requests": len(group), "prompt": L}):
            for lo in range(0, L, self.sched.prefill_chunk):
                hi = min(lo + self.sched.prefill_chunk, L)
                with self.obs.trace.span("prefill_chunk", {"start": lo}):
                    tok, marg, pools = self._prefill(
                        self.params, jnp.asarray(toks[:, lo:hi]), self.cache.pools,
                        self.cache.bt(), jnp.int32(lo), self._next_key(),
                    )
                    self.cache.pools = pools
            firsts, margs = (np.asarray(a) for a in jax.device_get((tok, marg)))
        self.stats["prefill_s"] += time.perf_counter() - t0
        self.stats["prefill_tokens"] += L * len(group)
        for slot, req in group:
            self.cache.lens[slot] = L
            req.margins.append(float(margs[slot]))
            if self.sched.record_token(slot, int(firsts[slot])):
                self._release_slot(slot)

    def pin_prompt(self, tokens) -> int:
        """Prefill a system preamble once and pin its full blocks in the
        radix prompt cache permanently (``--pin-prompt``): the chain is
        never evicted — not by block pressure, not by a burst of cold
        registrations — and does not count against the node cap.  Call
        before traffic (needs an idle engine: it borrows slot 0 for the
        prefill and releases it, leaving only the cache pins).  Returns the
        number of pinned tokens (full blocks only — the partial tail block,
        if any, is recomputed by adopters like any other resumed span)."""
        if not self.prefix_share:
            raise ValueError("pin_prompt requires prefix_share=True")
        tokens = _normalize_prompt(tokens, self.bos_id)
        if not self.sched.idle():
            raise RuntimeError("pin_prompt needs an idle engine (call pre-traffic)")
        if len(tokens) + 1 > self.max_seq:
            raise ValueError("pinned prompt exceeds max_seq")
        slot = 0
        self.cache.reset_slot(slot)
        self.cache.allocate(slot, len(tokens))
        for lo in range(0, len(tokens), self.sched.prefill_chunk):
            hi = min(lo + self.sched.prefill_chunk, len(tokens))
            self.cache.ensure_writable(slot, lo, hi)
            sub = self.cache.slice_slot(slot)
            _, _, new_pools = self._prefill(
                self.params, jnp.asarray(tokens[None, lo:hi]), sub,
                self.cache.bt_row(slot), jnp.int32(lo), self._next_key(),
            )
            self.cache.merge_slot(slot, new_pools)
        self.cache.lens[slot] = len(tokens)
        self.cache.register_prefix(slot, tokens, pinned=True)
        self.cache.release(slot)
        return (len(tokens) // self.cache.block_size) * self.cache.block_size

    def tick(self) -> int:
        """One decode step for every live slot (dead rows ride along writing
        into the trash block); returns the number of live slots advanced."""
        live = self.sched.live
        if not live:
            return 0
        tr = self.obs.trace
        tok_in = np.zeros((self.batch,), np.int32)
        with tr.span("cow_preflight", {"live": len(live)}):
            for i in live:
                tok_in[i] = self.sched.slots[i].last_token
                # a donor's decode write can land in a block a prefix-sharer
                # adopted — copy-on-write it out of the shared run first
                self.cache.ensure_writable(i, int(self.cache.lens[i]), int(self.cache.lens[i]) + 1)
        t0 = time.perf_counter()
        with tr.span("decode_tick", {"live": len(live)}):
            toks, margs, pools = self._decode(
                self.params, jnp.asarray(tok_in[:, None]), self.cache.pools,
                self.cache.bt(), jnp.asarray(self.cache.lens.copy()), self._next_key(),
            )
            self.cache.pools = pools
            # one host round-trip for ids + margins (decode stays two tiny arrays)
            out, marg = (np.asarray(a) for a in jax.device_get((toks, margs)))
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_tokens"] += len(live)
        self.stats["decode_dispatches"] += 1
        for i in live:
            self.cache.lens[i] += 1
            self.sched.slots[i].margins.append(float(marg[i]))
            if self.sched.record_token(i, int(out[i])):
                self._release_slot(i)
        return len(live)

    def megastep(self) -> int:
        """Up to ``decode_steps`` decode ticks for every live slot in ONE
        jitted dispatch (``_megastep_fn``); returns the number of live slots
        advanced.  The per-tick host work is hoisted to window entry:

        * **CoW preflight**: each slot's write span for the whole window —
          ``[lens, lens + min(N, remaining))`` — is made writable once via
          the batched ``ensure_writable`` (one pool rebuild), instead of one
          call per slot per tick.  The span never exceeds the slot's
          admission-time allocation because ``remaining`` caps it at
          ``max_new`` and the final emitted token is never consumed.
        * **one upload** of block tables / lens / masks, **one download** of
          ``(B, N)`` token ids + margins + emitted flags per window.

        The host then replays the emitted flags through
        ``Scheduler.record_token`` in tick order; because the device finish
        mask mirrors ``record_token`` exactly (EOS emit or ``max_new``
        reached), a finished row's later flags are False and the replay
        releases each slot at the same tick the per-tick path would have.
        """
        live = self.sched.live
        if not live:
            return 0
        tr = self.obs.trace
        N = self.decode_steps
        with tr.span("cow_preflight", {"live": len(live)}):
            for i in live:
                req = self.sched.slots[i]
                lo = int(self.cache.lens[i])
                self.cache.ensure_writable(i, lo, lo + min(N, req.max_new - len(req.generated)))
        t0 = time.perf_counter()
        mega = {"live": len(live), "steps": N}
        if self._kv_walk and tr.enabled:
            # the paged decode kernel's walk at the first tick: every row at
            # its length with this tick's token (a free slot walks its one
            # trash block), against the blocks its grid holds
            tokens, grid = self._kv_walk
            first, end = kv_block_range(self.cache.lens + 1, tokens)
            mega.update(kv_blocks_walked=int((end - first).sum()), kv_blocks_grid=grid)
        with tr.span("decode_megastep", mega):
            with tr.span("megastep_args"):
                tok_in = np.zeros((self.batch,), np.int32)
                active = np.zeros((self.batch,), bool)
                rem = np.zeros((self.batch,), np.int32)
                eos = np.full((self.batch,), -1, np.int32)  # -1: token ids are >= 0
                for i in live:
                    req = self.sched.slots[i]
                    tok_in[i] = req.last_token
                    active[i] = True
                    rem[i] = req.max_new - len(req.generated)
                    if req.eos_id is not None:
                        eos[i] = req.eos_id
                args = (jnp.asarray(tok_in), self.cache.pools, self.cache.bt(),
                        jnp.asarray(self.cache.lens.copy()), jnp.asarray(active),
                        jnp.asarray(rem), jnp.asarray(eos), self._next_key())
            toks, margs, emitted, pools = self._megadecode(self.params, *args)
            self.cache.pools = pools
            with tr.span("megastep_sync"):
                out, marg, em = (np.asarray(a) for a in jax.device_get((toks, margs, emitted)))
        dt = time.perf_counter() - t0
        total = 0
        replayed = {"tokens": 0, "released": 0}
        with tr.span("replay", replayed):
            for j in range(N):
                for i in live:
                    if not em[i, j]:
                        continue
                    total += 1
                    self.cache.lens[i] += 1
                    self.sched.slots[i].margins.append(float(marg[i, j]))
                    if self.sched.record_token(i, int(out[i, j])):
                        self._release_slot(i)
            replayed.update(tokens=total, released=len(live) - len(self.sched.live))
        self.stats["decode_s"] += dt
        self.stats["decode_tokens"] += total
        self.stats["decode_dispatches"] += 1
        return len(live)

    def _advance(self) -> int:
        """One decode round (subclass hook: the spec engine swaps in its
        draft-verify round here).  ``decode_steps > 1`` routes to the fused
        megastep; 1 keeps the per-tick path (and its per-token parity role)."""
        if self.decode_steps > 1:
            return self.megastep()
        return self.tick()

    def step(self) -> int:
        """Admit what fits, then advance one decode round."""
        tr = self.obs.trace
        with tr.span("engine_step"):
            admission = {"admitted": 0, "queued": 0}
            with tr.span("admission", admission):
                admitted = self.sched.admissions(self._admission_gate())
                admission.update(admitted=len(admitted), queued=len(self.sched.queue))
            if self.sched.lockstep:
                if admitted:
                    self._admit_group(admitted)
            else:
                for slot, req in admitted:
                    self._admit(slot, req)
            n = self._advance()
        if n == 0 and not admitted and self.sched.queue:
            raise RuntimeError("scheduler stalled: queued work but nothing admittable")
        return n

    def generate(self, prompts: list, max_new: int = 16) -> list[list[int]]:
        """Convenience batch API: submit all, step until drained."""
        reqs = [
            Request(uid=i, prompt=_normalize_prompt(p, self.bos_id), max_new=max_new)
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            self.submit(r)
        self.last_requests = reqs  # parity gates read tokens + margins here
        while not self.sched.idle():
            self.step()
        return [r.generated for r in reqs]
