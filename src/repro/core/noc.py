"""The NoC layer: Azul's send/recv message passing on the ICI torus.

Azul synchronizes PEs *only* through network messages (custom send/recv
RISC-V instructions over a 2D-torus NoC).  Under ``shard_map`` the same
role is played by ``jax.lax`` collectives over named mesh axes; this module
wraps them in a send/recv-flavoured API so the engine reads like the
paper's programming model:

  neighbor_shift    -- one torus hop (ppermute), Azul's point-to-point send
  pull_shard        -- receive the shard a fixed hop count away: one step
                       of a compiled halo-exchange schedule (commplan)
  gather_cols/rows  -- assemble an x halo along a mesh axis (all_gather)
  reduce_rows       -- combine partial y fragments (psum / psum_scatter)
  mesh_transpose    -- the (i, j) -> (j, i) vector-layout swap between the
                       SpMV output layout (row blocks) and input layout
                       (column blocks); a single permutation step on the
                       torus, the analogue of Azul's x redistribution.
  bcast_from        -- one tile broadcasting a solved block (SpTRSV stages)

All functions must be called *inside* shard_map with the axis names bound.
Single-tile axes degenerate gracefully: every permutation helper returns
its input unchanged (no ppermute emitted) when the hop is an identity --
p == 1 meshes and zero shifts cost nothing on the NoC.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = [
    "neighbor_shift",
    "pull_shard",
    "gather_along",
    "reduce_along",
    "reduce_scatter_along",
    "mesh_transpose",
    "reverse_vector",
    "bcast_from",
    "axis_coord",
]


def axis_coord(axis: str) -> jnp.ndarray:
    """This tile's coordinate along a mesh axis (Azul's row/col id fields)."""
    return lax.axis_index(axis)


def _ppermute(x: jnp.ndarray, axes, perm) -> jnp.ndarray:
    """ppermute that elides identity permutations (p == 1 axes, zero
    shifts): the NoC hop disappears instead of becoming a no-op message."""
    if all(s == d for s, d in perm):
        return x
    return lax.ppermute(x, axes, perm)


def neighbor_shift(x: jnp.ndarray, axis: str, shift: int = 1) -> jnp.ndarray:
    """One torus hop along ``axis`` (wraps around) -- a single Azul send."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return _ppermute(x, axis, perm)


def pull_shard(x: jnp.ndarray, axes, delta: int) -> jnp.ndarray:
    """Every tile receives the shard ``delta`` hops up ``axes``: tile ``i``
    gets tile ``(i + delta) % p``'s ``x``.  One step of a compiled halo
    pull schedule (:mod:`repro.core.commplan`); identity hops (p == 1,
    delta % p == 0) emit no ppermute."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    p = lax.axis_size(axes)
    perm = [((i + delta) % p, i) for i in range(p)]
    return _ppermute(x, axes, perm)


def gather_along(
    x: jnp.ndarray, axis: str, tiled: bool = True, vec_axis: int = 0
) -> jnp.ndarray:
    """Assemble the x halo along a mesh axis (concat of every tile's shard).

    ``vec_axis`` names the *array* axis that carries the distributed vector;
    batch-stacked shards of shape (k, u) pass ``vec_axis=1`` so the k RHS
    travel as one message while the batch axis stays intact."""
    return lax.all_gather(x, axis, axis=vec_axis, tiled=tiled)


def reduce_along(x: jnp.ndarray, axis) -> jnp.ndarray:
    """Combine partial products across ``axis`` (full copy on every tile)."""
    return lax.psum(x, axis)


def reduce_scatter_along(
    x: jnp.ndarray, axis: str, vec_axis: int = 0
) -> jnp.ndarray:
    """Combine partials across ``axis``, each tile keeping only its shard.

    ``vec_axis`` is the scattered array axis (see ``gather_along``): batched
    (k, br) partials scatter the trailing axis, yielding (k, u) shards."""
    return lax.psum_scatter(x, axis, scatter_dimension=vec_axis, tiled=True)


def mesh_transpose(x: jnp.ndarray, row_axes, col_axes) -> jnp.ndarray:
    """Vector-layout swap between SpMV's output (row-block, "L_row") and
    input (column-block, "L_col") distributions.

    With u-sized subsegments, L_row places segment ``q = i*pc + j`` on tile
    (i, j); L_col needs segment ``q = j*pr + k`` on tile (k, j).  The move is
    a single deterministic ``ppermute`` over the flattened mesh (every tile
    sends and receives exactly one u-shard) -- the analogue of Azul's x
    redistribution between solver steps.  Works for any (pr x pc), square or
    not.  The permutation moves each tile's whole shard, so batch-stacked
    (k, u) shards ride the same single hop unchanged.
    """
    row_axes = (row_axes,) if isinstance(row_axes, str) else tuple(row_axes)
    col_axes = (col_axes,) if isinstance(col_axes, str) else tuple(col_axes)
    pr = lax.axis_size(row_axes)
    pc = lax.axis_size(col_axes)
    # src tile holds segment q (flat id q = i*pc + j); dest tile for segment
    # q = j*pr + k is (k, j) = flat k*pc + j.  Degenerate grids (pr == 1 or
    # pc == 1, incl. the single-tile mesh) make this the identity -- elided.
    perm = [(j * pr + k, k * pc + j) for k in range(pr) for j in range(pc)]
    return _ppermute(x, row_axes + col_axes, perm)


def reverse_vector(x: jnp.ndarray, axes, vec_axis: int = 0) -> jnp.ndarray:
    """Globally reverse a vector stored in contiguous (L_row) shards: shard q
    swaps with shard P-1-q (one ppermute) and flips locally.  Used by the
    IC(0) preconditioner's L^T solve (run as a reversed lower solve).

    ``vec_axis`` names the *array* axis carrying the distributed vector
    (batch-stacked (k, u) shards pass ``vec_axis=1`` so the local flip
    reverses each RHS, not the batch).  p == 1 reduces to the local flip
    alone -- no ppermute."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    p = lax.axis_size(axes)
    perm = [(p - 1 - q, q) for q in range(p)]
    return jnp.flip(_ppermute(x, axes, perm), axis=vec_axis)


def bcast_from(x: jnp.ndarray, axis, src: jnp.ndarray | int) -> jnp.ndarray:
    """Broadcast ``x`` from the tile at coordinate ``src`` along ``axis``
    to every tile on that axis (masked psum -- single collective)."""
    me = lax.axis_index(axis)
    contrib = jnp.where(me == src, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)
