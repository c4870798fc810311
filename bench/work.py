"""Operations and bytes that the traced part of a window had to do, from the
shapes of the work alone, for the roofline and utilization readers.

Each count is what the algorithm needs, not what an implementation happens
to move: an int8 matmul reads its int8 weights once, its inputs and its
per-channel scales, and writes its outputs; paged attention reads each live
token's key and value codes and scales once.  Padding and re-reads are the
kernel's own loss and show as a lower share of its roofline.
"""

from __future__ import annotations

from bench.model import linear_shapes


def int_matmul(M: int, K: int, N: int, x_bytes: int, out_bytes: int) -> tuple[float, float]:
    """``(ops, bytes)`` of one W8A8 matmul of ``M`` rows: ``x (M, K)`` against
    int8 ``w (K, N)`` with a float32 scale per output channel."""
    return 2.0 * M * K * N, float(K * N + M * K * x_bytes + M * N * out_bytes + 4 * N)


def paged_attention(ctx_tokens: int, rows: int, heads: int, kv_heads: int, head_dim: int,
                    code_bytes: float = 1.0) -> tuple[float, float]:
    """``(ops, bytes)`` of one decode step of paged attention in one layer:
    ``rows`` queries over ``ctx_tokens`` cached tokens in all, int8 key and
    value codes with a float32 scale per token and KV head."""
    ops = 4.0 * heads * head_dim * ctx_tokens
    kv = ctx_tokens * kv_heads * (2 * head_dim * code_bytes + 2 * 4)
    q_and_out = rows * heads * head_dim * 2 * 2  # bfloat16 in and out
    return ops, float(kv + q_and_out)


def _linears(cfg: dict) -> list:
    out = list(linear_shapes(cfg).values())
    return out


def decode_tick(cfg: dict, rows: int, ctx_tokens: int) -> dict:
    """The work of one decode step of ``rows`` live sequences holding
    ``ctx_tokens`` cached tokens in all, by kernel, plus the whole step
    (``model``: every weight read once, every live token's cache read once).
    Rows that coast in the megastep after their request ended are not work."""
    L = cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim", cfg["hidden_size"] // H)
    mm_ops = mm_bytes = 0.0
    for K, N in _linears(cfg):
        o, b = int_matmul(rows, K, N, 4, 2)
        mm_ops += L * o
        mm_bytes += L * b
    if not cfg["tie_word_embeddings"]:
        o, b = int_matmul(rows, cfg["hidden_size"], cfg["vocab_size"], 4, 2)
        mm_ops += o
        mm_bytes += b
    at_ops, at_bytes = paged_attention(ctx_tokens, rows, H, KV, Dh)
    return {"int_matmul": (mm_ops, mm_bytes),
            "paged_attention": (L * at_ops, L * at_bytes),
            "model": (mm_ops + L * at_ops, mm_bytes + L * at_bytes)}


def prefill_chunk(cfg: dict, tokens: int, start: int) -> dict:
    """The work of one prefill chunk of ``tokens`` positions after ``start``
    cached ones: its int8 matmuls (``M = tokens``) and the whole chunk."""
    L = cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim", cfg["hidden_size"] // H)
    mm_ops = mm_bytes = 0.0
    for K, N in _linears(cfg):
        o, b = int_matmul(tokens, K, N, 4, 2)
        mm_ops += L * o
        mm_bytes += L * b
    if not cfg["tie_word_embeddings"]:
        o, b = int_matmul(tokens, cfg["hidden_size"], cfg["vocab_size"], 4, 2)
        mm_ops += o
        mm_bytes += b
    # causal attention over the cache and the chunk itself
    keys = tokens * start + tokens * (tokens + 1) / 2
    at_ops = 4.0 * H * Dh * keys
    at_bytes = (start + tokens) * KV * (2 * Dh + 8)
    return {"int_matmul": (mm_ops, mm_bytes), "model": (mm_ops + L * at_ops, mm_bytes + L * at_bytes)}


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward and backward: ``6 N`` for
    the ``N`` weights a token multiplies (the tied embedding counts once, as
    the output head), plus causal attention's ``6 * 2 * L * d * seq / 2``.
    Recomputation is not counted."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    n = L * sum(K * N for K, N in _linears(cfg)) + d * cfg["vocab_size"]
    H = cfg["num_attention_heads"]
    Dh = cfg.get("head_dim", d // H)
    attn = 6.0 * 2.0 * L * H * Dh * seq / 2.0
    return 6.0 * n + attn


def add(acc: dict, part: dict) -> None:
    for k, (o, b) in part.items():
        o0, b0 = acc.get(k, (0.0, 0.0))
        acc[k] = (o0 + o, b0 + b)


def least_seconds(ops: float, nbytes: float, peak_ops: float, peak_bytes_s: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and which."""
    tc, tm = ops / peak_ops, nbytes / peak_bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")


def window_work(rec) -> dict:
    """The work of the traced part of a run's window, by kernel and for the
    whole model, from the benchmark's own step records."""
    cfg = rec.cell.config
    t0, t1 = rec.trace_window
    acc: dict = {}
    for s in rec.counters.get("steps", []):
        if s["t0"] < t0 or s["t1"] > t1:
            continue
        for j in range(s.get("ticks", 0)):
            add(acc, decode_tick(cfg, s["live"], s["ctx"] + j * s["live"]))
        for tokens, start in s.get("prefill", []):
            add(acc, prefill_chunk(cfg, tokens, start))
    return acc
