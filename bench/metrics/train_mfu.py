"""Whole training step: model FLOPs (``work.train_flops_per_token``: 6 x the
weights a token multiplies, plus causal attention; recomputation not
counted) of the steps in the traced window, over the traced window's length
and the chip's bf16 peak."""

from bench import work


def read(rec):
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    t0, t1 = rec.trace_window
    steps = [s for s in rec.counters.get("steps", []) if s["t0"] >= t0 and s["t1"] <= t1]
    if not steps:
        return None
    c = rec.counters
    flops = work.train_flops_per_token(rec.cell.config, c["seq"]) * c["tokens_per_step"] * len(steps)
    return 100.0 * flops / rec.trace.window_s / rec.peaks["bf16_flops"]
