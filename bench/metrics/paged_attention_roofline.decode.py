"""Kernel ``kernels/paged_attention.py`` (decode attention over int8 paged
KV): the least time its calls in the traced window could take on the chip
(each live token's key and value codes and scales read once, ``work.py``)
over the device time of its calls in the trace: the Pallas custom calls
that take a rank-4 int8 pool operand."""

from bench import work

POOL = r's8\[\d+,\d+,\d+,\d+\]'


def read(rec):
    import re

    if rec.trace is None or "paged_attention" not in rec.work:
        return None
    pool = re.compile(POOL)
    t = sum(v for k, v in rec.trace.ops.items() if "tpu_custom_call" in k and pool.search(k))
    if t <= 0:
        return None
    ops, nbytes = rec.work["paged_attention"]
    least, _ = work.least_seconds(ops, nbytes, rec.peaks["int8_ops"], rec.peaks["hbm_bytes_s"])
    return 100.0 * least / t
