"""The least HBM traffic of the multigrid smoother in one V-cycle (one per
PCG iteration), whatever implements it.

    per sweep at level l = nnz_l x value bytes    (the level's values)
                         + 3 x n_l x value bytes  (r in, x in, x out)

A V-cycle runs two symmetric Gauss-Seidel smoothings -- four sweeps -- on
every level but the coarsest, and one smoothing -- two sweeps -- there.
No column index is counted, so a store that reads none cannot read above
100% of this bound; nor is the residual at the coarse rows, the transfer
between levels or any gathered copy of x.
"""

from __future__ import annotations

import math

import numpy as np


def sweeps(level: int, n_levels: int) -> int:
    return 2 if level == n_levels - 1 else 4


def per_vcycle(cfg: dict) -> dict:
    """{"levels": [{"n", "nnz", "sweeps", "bytes"}], "bytes"} of one
    V-cycle of the configuration's hierarchy."""
    from chipbench.harness import plugin

    op = plugin("operators", cfg["operator"]["kind"])
    value = np.dtype(cfg["solver"]["dtype"]).itemsize
    grids = op.level_dims(cfg)
    levels = []
    for lv, g in enumerate(grids):
        n = math.prod(g)
        nnz = math.prod(3 * d - 2 for d in g)
        s = sweeps(lv, len(grids))
        levels.append({"n": n, "nnz": nnz, "sweeps": s,
                       "bytes": s * (nnz + 3 * n) * value})
    return {"levels": levels, "bytes": sum(v["bytes"] for v in levels)}
