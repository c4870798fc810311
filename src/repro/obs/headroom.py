"""Accumulator-headroom telemetry: the paper's overflow guarantee as a
runtime observable.

A2Q proves overflow avoidance *statically* — the deployed integer weights'
per-channel l1 norms fit the Eq. 15 budget for the target accumulator width
``P``.  This module turns that proof into gauges the serve stack exports:

* :func:`static_headroom_report` — walks a deployed param tree (``q8``/``s8``
  leaves from ``serve.engine.deploy_params``) and computes each layer's
  worst-case bound utilization ``||q8||_1 * 2**(N - 1_signed) / (2**(P-1)-1)``
  (``core.bounds.headroom_utilization``, the ratio form of Eq. 11).
  Utilization < 1.0 on every layer *is* the guarantee.
* :func:`observed_headroom` — drives one eager forward through the fused
  W8A8 path inside ``nn.linear.acc_probe_scope`` and samples the actual
  integer operands' worst partial-sum magnitude ``max(|x_codes| @ |q8|)``
  per call site — always <= the static bound when the guarantee holds, so
  ``observed > bound`` is a hard violation.
* :func:`engine_headroom` — populates an engine's metrics registry
  (``acc_headroom_utilization{site=...}``, ``acc_observed_max{site=...}``,
  ``acc_bound{site=...}``, ``acc_headroom_util_max``,
  ``acc_headroom_violations``) and returns a summary dict.  CI's obs-smoke
  job and ``benchmarks/run.py`` gate ``acc_headroom_violations == 0``.

Sites inside vmapped/scanned layer stacks trace with abstract operands, so
the eager probe skips them; the static report still covers every deployed
layer (stacked leaves reduce per-channel l1 over all stack members).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bounds import headroom_utilization, l1_budget

__all__ = ["static_headroom_report", "observed_headroom", "engine_headroom"]


def _deployed_signed(path: tuple) -> bool:
    # mirror of deploy_params: rwkv6's channel-mix wv consumes unsigned
    # (post-relu^2) activations; everything else is signed
    return not (len(path) >= 2 and path[-2] == "cm" and path[-1] == "wv")


@jax.jit
def _l1_max(q8):
    # weights are (..., K, C): channels (accumulators) on the last axis, so
    # per-channel l1 reduces the K axis.  Reduced on the device in int32
    # (127 * K is far below 2**31), so no weight copy reaches the host.
    return jnp.max(jnp.sum(jnp.abs(q8.astype(jnp.int32)), axis=-2))


def static_headroom_report(params: dict, quant) -> list:
    """Per-layer worst-case accumulator utilization for a deployed tree.

    One record per ``q8`` leaf (stacked leaves collapse to their worst
    channel across all stack members)::

        {"site", "utilization", "l1_max", "l1_budget", "acc_bits",
         "in_bits", "in_signed"}
    """
    P = quant.acc_bits if quant.mode == "a2q" else 32
    N = quant.act_bits
    out: list = []

    def walk(node, path=()):
        if not isinstance(node, dict):
            return
        if "q8" in node and "s8" in node:
            signed = _deployed_signed(path)
            l1_max = float(_l1_max(node["q8"])) if node["q8"].size else 0.0
            out.append({
                "site": ".".join(path),
                "utilization": float(headroom_utilization(l1_max, N, signed, P)),
                "l1_max": l1_max,
                "l1_budget": l1_budget(P, N, signed),
                "acc_bits": P,
                "in_bits": N,
                "in_signed": signed,
            })
            return
        for k, v in node.items():
            walk(v, path + (k,))

    walk(params)
    return out


def observed_headroom(
    arch,
    params: dict,
    *,
    rt=None,
    tokens: Optional[np.ndarray] = None,
    batch: int = 1,
    seq: int = 8,
    seed: int = 0,
) -> list:
    """Sample observed accumulator magnitudes from one eager forward.

    Returns the probe records from :func:`nn.linear.acc_probe_scope` —
    empty when ``rt.int_forward`` is off (the fused path never runs) or
    every deployed site sits inside a vmapped stack.
    """
    from repro.models.lm import apply_lm
    from repro.nn.linear import acc_probe_scope

    if tokens is None:
        tokens = jax.random.randint(
            jax.random.PRNGKey(seed), (batch, seq), 0, arch.vocab, dtype=jnp.int32
        )
    samples: list = []
    with acc_probe_scope(samples):
        apply_lm(params, arch, tokens=jnp.asarray(tokens), rt=rt)
    return samples


def engine_headroom(engine, *, seq: int = 8, seed: int = 0) -> dict:
    """Populate an engine's metrics registry with headroom gauges.

    Static gauges cover every deployed layer; observed gauges cover the
    eager-probeable fused sites.  ``acc_headroom_violations`` counts static
    utilizations > 1.0 plus observed samples exceeding their bound — zero
    whenever the A2Q constraint actually held at deployment.
    """
    m = engine.obs.metrics
    quant = engine.arch.quant
    static = static_headroom_report(engine.params, quant)
    observed = observed_headroom(
        engine.arch, engine.params, rt=engine.rt, seq=seq, seed=seed
    )
    violations = 0
    util_max = 0.0
    for rec in static:
        m.gauge("acc_headroom_utilization", {"site": rec["site"]}).set(rec["utilization"])
        util_max = max(util_max, rec["utilization"])
        if rec["utilization"] > 1.0:
            violations += 1
    obs_max = 0.0
    for rec in observed:
        site = rec["site"] or "<unlabeled>"
        m.gauge("acc_observed_max", {"site": site}).set(rec["acc_max"])
        m.gauge("acc_bound", {"site": site}).set(rec["bound"])
        if rec["bound"] > 0:
            obs_max = max(obs_max, rec["acc_max"] / rec["bound"])
        if rec["acc_max"] > rec["bound"]:
            violations += 1
    m.gauge("acc_headroom_util_max").set(util_max)
    m.gauge("acc_observed_frac_max").set(obs_max)
    m.counter("acc_headroom_violations").set(violations)
    return {
        "layers": len(static),
        "observed_sites": len(observed),
        "util_max": util_max,
        "observed_frac_max": obs_max,
        "violations": violations,
    }
