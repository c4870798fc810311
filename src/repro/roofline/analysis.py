"""Three-term roofline from the compiled dry-run artifact.

    compute    = FLOPs / (chips * peak)
    memory     = HBM bytes / (chips * HBM bw)
    collective = collective bytes / (chips * link bw)

Sources: ``compiled.cost_analysis()`` provides per-device FLOPs and bytes
(XLA's post-partitioning module is the per-device program, so these are
already divided by the mesh).  Collective bytes are NOT in cost_analysis:
``collective_bytes_from_hlo`` reads the optimized HLO through
``dist.census`` (one parser for the launcher's census and the dry-run) and
sums the result sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, a loop body once per trip (a consistent
per-chip wire-bytes proxy: ring all-reduce moves ~2x the buffer, all-gather
~1x the result — we record raw result bytes per op kind so any convention
can be recomputed; the *deltas* the perf loop optimizes are
convention-independent).

``MODEL_FLOPS = 6*N*D`` (dense) / ``6*N_active*D`` (MoE) gives the useful-work
ratio that catches remat/redundancy waste.

Compressed-gradient classification: ``dist.collectives.compressed_psum`` puts
the data-parallel gradient on the wire as s8/s16 integers (an all-to-all plus
an all-gather per leaf).  No other path in the repo moves low-bit *integers*
through a collective, so an s8/s16/u8/u16 all-gather / all-to-all IS gradient
traffic — ``collective_bytes_from_hlo`` reports it separately as
``gradient_wire_bytes`` so the dry-run can price the gradient path on its own.
``wire_bytes`` converts raw result bytes into the ring-algorithm wire
convention (all-reduce moves ~2x its buffer, everything else ~1x), which is
the basis for the ``wire_bytes_saved`` number the dry-run records.
"""

from __future__ import annotations

from repro.dist.census import KINDS, collective_ops
from repro.roofline import hw

__all__ = ["collective_bytes_from_hlo", "wire_bytes", "roofline_terms", "model_flops"]

_GRADIENT_WIRE_DTYPES = ("s8", "u8", "s16", "u16")


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum collective result bytes by op kind over an optimized HLO module,
    as ``dist.census`` counts them (a ``while`` body once per trip, an async
    pair once, at its ``-done``).

    Low-bit integer (s8/s16) all-gather / all-to-all results are additionally
    classified as compressed-gradient traffic (``gradient_wire_bytes``): only
    ``dist.collectives`` puts integer payloads that narrow on the wire.
    """
    per_kind = {k: 0 for k in KINDS}
    counts = {k: 0 for k in KINDS}
    gradient_wire = 0
    gradient_count = 0
    for kind, arrays, times in collective_ops(hlo_text):
        per_kind[kind] += times * sum(n for _, n in arrays)
        counts[kind] += times
        int_bytes = sum(n for dtype, n in arrays if dtype in _GRADIENT_WIRE_DTYPES)
        if int_bytes and kind in ("all-gather", "all-to-all"):
            gradient_wire += times * int_bytes
            gradient_count += times
    return {
        "bytes_by_kind": per_kind,
        "counts": counts,
        "total_bytes": sum(per_kind.values()),
        "gradient_wire_bytes": gradient_wire,
        "gradient_wire_counts": gradient_count,
    }


# Ring-algorithm wire weight per result byte: a ring all-reduce moves
# ~2x its buffer (reduce-scatter pass + all-gather pass); gather/scatter/
# permute collectives move ~1x their result.
_WIRE_WEIGHT = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def wire_bytes(collectives: dict) -> float:
    """Result-byte record -> estimated per-chip wire bytes (ring convention)."""
    return sum(
        _WIRE_WEIGHT.get(kind, 1.0) * b
        for kind, b in collectives["bytes_by_kind"].items()
    )


def model_flops(n_params: float, tokens: float, kind: str = "train") -> float:
    """6*N*D for training; 2*N*D for a forward/decode pass."""
    factor = 6.0 if kind == "train" else 2.0
    return factor * n_params * tokens


def roofline_terms(
    *,
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
    n_chips: int,
    links_per_chip: int = 4,
) -> dict:
    """Seconds per step for each roofline term, per chip."""
    compute = flops_per_device / hw.PEAK_FLOPS_BF16
    memory = bytes_per_device / hw.HBM_BW
    collective = collective_bytes_per_device / (hw.ICI_LINK_BW * links_per_chip)
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    terms.update(
        dominant=dominant,
        bound_s=bound,
        # fraction of roofline: how close the *dominant* term is to being the
        # only cost — bound/(sum) == 1 means perfectly balanced on one wall.
        roofline_fraction=(compute / bound) if bound > 0 else 0.0,
        n_chips=n_chips,
    )
    return terms
