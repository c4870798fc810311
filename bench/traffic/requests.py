"""Serving traffic from a workload file: lengths and tokens.

Every size is fixed by the workload file and not by ``--seed``: each length
distribution is cut into ``levels`` equal-probability bins and a request
takes the median of its bin, so the set of lengths (and so the set of
prefill shapes the set-up warms) is the cell's own.  The seed only places
the first requests in the slots and draws the tokens, so every seed offers
the same work.

Length specs (``prompt``, ``output`` in a workload file)::

    {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32, "max": 2048, "levels": 16}
    {"dist": "uniform", "min": 1024, "max": 2048, "levels": 16}
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np
from scipy.special import ndtri


def length_levels(spec: dict) -> list:
    """The ``levels`` lengths of a length spec: the median of each of
    ``levels`` equal-probability bins, clipped to ``[min, max]``."""
    n = int(spec["levels"])
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        vals = [spec["median"] * math.exp(spec["sigma"] * float(ndtri(q))) for q in qs]
    elif spec["dist"] == "uniform":
        vals = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(max(round(v), spec["min"]), spec["max"])) for v in vals]


def _cycle(levels: list, n: int) -> list:
    return [levels[i % len(levels)] for i in range(n)]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


@dataclasses.dataclass
class RequestSpec:
    uid: int
    prompt: np.ndarray          # int32 tokens
    max_new: int                # tokens the program serves
    context: Optional[np.ndarray] = None  # tokens standing for an earlier part of the answer


def _tokens(rng, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, (n,), dtype=np.int64).astype(np.int32)


def offline_backlog(w: dict, seed: int, vocab: int, slots: int) -> tuple[list, list]:
    """``(initial, backlog)`` of an offline batch job.

    ``initial`` fills every slot at the window's start: each request is
    already part-way through its answer, by a span drawn over its life in
    whole prefill chunks (so its prefill shapes are the prompts' own), and
    the tokens of that span stand in for what it has generated so far.
    ``backlog`` is the queue behind them, longer than the window can drain.
    """
    chunk = int(w["prefill_chunk"])
    prompts = _cycle(length_levels(w["prompt"]), slots + int(w["backlog"]))
    outputs = _cycle(length_levels(w["output"]), slots + int(w["backlog"]))
    # steady state: the slots are spread evenly over the answers' lives
    spans = [chunk * int(((i + 0.5) / slots) * outputs[i] // chunk) for i in range(slots)]
    perm0 = rng_for(seed, 0).permutation(slots)
    # the queue's order is the cell's own, not the seed's: the requests that
    # refill slots inside the window, and so the window's work, are the same
    # for every seed
    perm1 = slots + rng_for(0, 0).permutation(int(w["backlog"]))
    tok = rng_for(seed, 1)
    initial = []
    for j, i in enumerate(perm0):
        span = min(spans[i], outputs[i] - 1)
        initial.append(RequestSpec(uid=j, prompt=_tokens(tok, vocab, prompts[i]),
                                   context=_tokens(tok, vocab, span), max_new=outputs[i] - span))
    backlog = [RequestSpec(uid=slots + j, prompt=_tokens(tok, vocab, prompts[i]), max_new=outputs[i])
               for j, i in enumerate(perm1)]
    return initial, backlog


def prefill_shapes(lengths, chunk: int) -> list:
    """The chunk lengths the engine's chunked prefill runs for these prompt
    lengths: whole chunks and each distinct remainder."""
    out = set()
    for n in lengths:
        if n >= chunk:
            out.add(chunk)
        if n % chunk:
            out.add(n % chunk)
    return sorted(out)
