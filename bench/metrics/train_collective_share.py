"""Layer ``dist/sharding`` (GSPMD's cross-chip collectives of a
tensor-parallel training step): the percentage of the traced steps' device
time spent in collective operations on the ``XLA Ops`` line, averaged over
the cell's chips.  Their own time (an operation's span less the operations
nested in it, averaged over the chips by the reduction) over the chips'
busy time in the traced window.

An operation is a collective where its text names one of these kinds as
its opcode, plain or as an async ``-start`` / ``-done`` pair: all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all.  On four v5e
chips the A2Q training step's trace shows plain ``all-reduce`` operations
only.  A trace with no collective (one chip) has nothing to read: None."""

import re

COLLECTIVE = re.compile(r"^\S+ = .*? (all-reduce|all-gather|reduce-scatter|collective-permute|"
                        r"all-to-all)(-start|-done)?\(")


def read(rec):
    if rec.trace is None or rec.trace.busy_s <= 0:
        return None
    t = sum(v for k, v in rec.trace.ops.items() if COLLECTIVE.match(k))
    if t <= 0:
        return None
    return 100.0 * t / rec.trace.busy_s
