"""Pallas TPU kernels for the perf-critical compute hot spots, with jit'd
wrappers (ops.py) and pure-jnp oracles (ref.py).  Layers import from ops."""

from repro.kernels.ops import (  # noqa: F401
    a2q_quantize,
    causal_attention,
    int_matmul,
    paged_attention,
    rwkv6_scan,
)
