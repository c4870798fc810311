"""Production mesh factory.  A FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — only the dry-run (and a
real launcher) ever calls it."""

from __future__ import annotations

from repro.dist.sharding import make_mesh

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256-chip v5e pod) single-pod mesh, or 2x16x16 across 2 pods.

    Axes: ``data`` = FSDP+DP, ``model`` = TP/EP/split-KV, ``pod`` = outer DP
    (one DCN-crossing gradient reduction per step).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
