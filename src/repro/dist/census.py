"""Census of the cross-chip collectives a compiled program runs, by kind.

``collective_census(compiled.as_text())`` reads the optimized HLO module of
one compiled step and counts, for each kind (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all), how many collectives one
run of the program executes and the bytes they produce:

* An operation inside a ``while`` body counts once per trip, so a scan
  over layers counts once per layer.  The trips are the loop's
  ``known_trip_count`` where the text gives it (the CPU compiler), else the
  bound its condition compares the counter with, ``counter < N`` (the TPU
  compiler; a scan's counter starts at 0).  One inside any other called
  computation (a fusion, an async wrapper) counts as often as its caller
  runs.
* An async pair (``all-reduce-start`` / ``all-reduce-done``) counts once,
  at its ``-done``, whose result is what the collective produced; a plain
  operation counts at itself.
* Bytes are those of the result, every element of a tuple result added
  (the combiners put several operands into one collective).

Text with no computation headers (a list of instructions) is read as the
body of one entry computation run once.  ``collective_ops`` yields each
collective with its result arrays and its runs, for callers that split
the bytes further (``roofline.analysis`` prices integer gradient traffic
on its own).  A program on one device has no collectives, so every count
is 0.  Nothing here runs or compiles anything.
"""

from __future__ import annotations

import re
from typing import Iterator

__all__ = ["KINDS", "collective_census", "collective_ops"]

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP = re.compile(r"=\s*(.*?)\s(" + "|".join(KINDS) + r")(-start|-done)?\(")
_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_CALLEE = re.compile(r"\b(?:calls|to_apply|body|branch_computations|true_computation|"
                     r"false_computation)=\{?([%\w.\-, ]+)\}?")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_LESS_THAN = re.compile(r"ROOT .*compare\(%?[\w.\-]+, %?([\w.\-]+)\), direction=LT")


def _arrays(result: str) -> list[tuple[str, int]]:
    """``(dtype, bytes)`` of each array in an instruction's result type."""
    out = []
    for dtype, dims in _ARRAY.findall(result):
        n = _DTYPE_BYTES.get(dtype)
        if n is None:
            continue
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def _computations(hlo_text: str) -> tuple[dict, str]:
    """Each computation's instruction lines by name, and the entry's name;
    lines outside any computation make up the entry when there is none."""
    comps: dict = {"": []}
    entry, name = "", None
    for line in hlo_text.splitlines():
        if name is None:
            m = _HEADER.match(line)
            if m:
                name = m.group(1)
                comps[name] = []
                if line.startswith("ENTRY"):
                    entry = name
            else:
                comps[""].append(line)
        elif line.strip() == "}":
            name = None
        else:
            comps[name].append(line)
    return comps, entry


def _trips(line: str, comps: dict) -> int:
    """How many times the ``while`` on ``line`` runs its body."""
    known = _TRIPS.search(line)
    if known:
        return int(known.group(1))
    cond = comps.get(_CONDITION.search(line).group(1), ())
    bound = next((m.group(1) for m in map(_LESS_THAN.search, cond) if m), None)
    if bound is None:
        return 1
    const = re.compile(rf"%?{re.escape(bound)} = s32\[\]\S* constant\((\d+)\)")
    return next((int(m.group(1)) for m in map(const.search, cond) if m), 1)


def collective_ops(hlo_text: str) -> Iterator[tuple[str, list[tuple[str, int]], int]]:
    """``(kind, [(dtype, bytes) of each result array], runs)`` for each
    collective instruction of the module, ``runs`` being how many times one
    run of the module executes it."""
    comps, entry = _computations(hlo_text)

    def walk(name: str, times: int):
        for line in comps.get(name, ()):
            op = _OP.search(line)
            if op and op.group(3) != "-start":
                yield op.group(2), _arrays(op.group(1)), times
            body = _BODY.search(line)
            for callee in _CALLEE.findall(line):
                for c in callee.split(","):
                    c = c.strip().lstrip("%")
                    yield from walk(c, times * (_trips(line, comps) if body and body.group(1) == c else 1))

    yield from walk(entry, 1)


def collective_census(hlo_text: str) -> dict:
    """``{kind: {"count": n, "bytes": b}}`` for every kind in ``KINDS``: the
    collectives one run of the module executes and the bytes they produce."""
    out = {k: {"count": 0, "bytes": 0} for k in KINDS}
    for kind, arrays, times in collective_ops(hlo_text):
        out[kind]["count"] += times
        out[kind]["bytes"] += times * sum(n for _, n in arrays)
    return out
