"""Kernel ``kernels/int_matmul.py`` (fused W8A8 matmul): the least time its
calls in the traced window could take on the chip (the larger of their int8
operations over the int8 peak and their bytes over HBM bandwidth,
``work.py``), over the device time of its calls in the trace.

Its calls are the Pallas custom calls that take a rank-2 int8 operand (the
weight codes); paged attention's take rank-4 int8 pools."""

from bench import work

WEIGHT = r's8\[\d+,\d+\]'
POOL = r's8\[\d+,\d+,\d+,\d+\]'


def read(rec):
    import re

    if rec.trace is None or "int_matmul" not in rec.work:
        return None
    weight, pool = re.compile(WEIGHT), re.compile(POOL)
    t = sum(v for k, v in rec.trace.ops.items()
            if "tpu_custom_call" in k and weight.search(k) and not pool.search(k))
    if t <= 0:
        return None
    ops, nbytes = rec.work["int_matmul"]
    least, _ = work.least_seconds(ops, nbytes, rec.peaks["int8_ops"], rec.peaks["hbm_bytes_s"])
    return 100.0 * least / t
