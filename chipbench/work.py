"""The least HBM traffic of one Jacobi-PCG iteration, per right-hand side,
whatever implements it.

    operator bytes = true nnz x (value bytes + 4-byte column index)
                     (0 for a matrix-free operator)
    vector bytes   = 12 n-vector passes x n x value bytes

The 12 passes are the fused, p-folded recurrence's least vector traffic:
the matvec with its fold reads z and p and writes p and Ap (4); the update
reads x, r, p, Ap and the inverse diagonal and writes x, r and z (8).  The
model assumes every n-vector pass streams from HBM.  It counts no padding
and no gathered copy of x, so a change of storage format or the removal
of a per-index gather raises the share of this bound that a run reaches.
An implementation that kept the CG state in VMEM across iterations would
read above 100%, and the count would then have to change.
"""

from __future__ import annotations

import numpy as np

INDEX_BYTES = 4
# one iteration's n-vector passes, written out
VECTOR_PASSES = {
    "matvec_fold": ("z in", "p in", "p out", "Ap out"),
    "cg_update": ("x in", "r in", "p in", "Ap in", "inverse diagonal in",
                  "x out", "r out", "z out"),
}


def vector_passes() -> int:
    return sum(len(v) for v in VECTOR_PASSES.values())


def per_iteration(cfg: dict) -> dict:
    """{"operator_bytes", "vector_bytes", "bytes"} of one iteration of one
    right-hand side of the configuration."""
    from chipbench.harness import plugin

    op = plugin("operators", cfg["operator"]["kind"])
    value = np.dtype(cfg["solver"]["dtype"]).itemsize
    n = op.n(cfg)
    operator = op.nnz(cfg) * (value + INDEX_BYTES) if op.stored(cfg) else 0
    vector = vector_passes() * n * value
    return {"operator_bytes": operator, "vector_bytes": vector,
            "bytes": operator + vector}
