"""The reader of ``train_collective_share`` on traces recorded on TPU v5e
chips: a slice of one traced ``yi6b-train-tp4`` step on each of its four
chips (``data/train_tp4_trace_events.json``: every operation of the slice
on each chip's ``XLA Ops`` line, the program runs cut to the slice, and the
step's ``bench.step`` annotation cut to it too), and the one-chip smollm
training step (``data/train_step_trace_events.json``), which has no
collective."""

from __future__ import annotations

import pathlib
import re

import pytest

from bench import core, trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
READER = core.load_module(core.BENCH_DIR / "metrics" / "train_collective_share.py")
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")

# Measured by hand on the four-chip slice (10 ms of the backward pass, in
# which each chip is busy throughout): each chip runs three all-reduces of a
# bf16[4,4096,4096] activation gradient and no other collective, of
# 7,082,841 / 7,088,132 / 7,081,147 / 7,084,833 ns on chips 0-3, so
# 70.82841 / 70.88132 / 70.81147 / 70.84833% of the slice; their mean.
# (Over the two whole traced steps the reader read 12.086%.)
HAND_SHARE = 70.8423825


def _record(name: str, n_chips: int):
    rec = core.RunRecord(cell=None)
    rec.trace = tr.reduce_events(tr.read_events(DATA / name), n_chips)
    return rec


def _by_hand(events) -> float:
    step = next(e for e in events if e[2] == tr.STEP)
    w0, w1 = step[3], step[3] + step[4]
    chips = sorted({e[0] for e in events if e[0].startswith("/device:")})
    opcode = re.compile(r"^\S+ = .*? ([a-z\-]+)\(")
    shares = []
    for chip in chips:
        t = sum(e[4] for e in events if e[0] == chip and e[1] == "XLA Ops"
                and opcode.match(e[2]) and opcode.match(e[2]).group(1).replace("-start", "")
                .replace("-done", "") in KINDS)
        shares.append(100.0 * t / (w1 - w0))
    return sum(shares) / len(shares)


def test_reads_the_share_measured_by_hand():
    events = tr.read_events(DATA / "train_tp4_trace_events.json")
    assert len({e[0] for e in events if e[0].startswith("/device:")}) == 4
    value = READER.read(_record("train_tp4_trace_events.json", 4))
    assert value == pytest.approx(HAND_SHARE, rel=1e-6)
    assert value == pytest.approx(_by_hand(events), rel=1e-9)


def test_nothing_to_read_on_one_chip():
    assert READER.read(_record("train_step_trace_events.json", 1)) is None
