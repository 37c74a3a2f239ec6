"""Grouped matrix products over rows sorted by group: the experts'
feed-forward of ``models/latent_moe.held_experts``.

``x [R, K]`` holds the rows of group 0, then of group 1, ... (``sizes`` of
them, data); group ``g``'s rows are multiplied by ``w[base + g] [K, N]`` of a
stack ``[G, K, N]`` that is read where it lies.  The rows are cut into tiles
of ``tile``; a :func:`plan` lists the (group, tile) pairs that hold any row —
a tile that two groups share once for each, an empty group never — and a
kernel walks that list (its length is data: the grid's bound), one step a
pair and a panel of ``N``: the whole ``K`` at once, so a group's panel
changes only at the group's edge and its matrix is read once however many
tiles its rows span, never if it has none.  A step writes the rows of its
own group and leaves the tile's other rows as they are; rows past the last
group's are never written (whoever reads the result masks them).

The kernel is megablox ``gmm``'s walk (``jax.experimental.pallas.ops.tpu``)
cut to what the expert layer needs — no tiling of ``K``, so no accumulator;
ONE plan for the layer's products; the gate and the up product in one step
with their ``silu(g) * u`` — because a program is traced and lowered at
every start (``setup_s``), and ``gmm`` x 3 with a plan each cost four times
the expert layer's own trace (PERF.md, PR 39).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pdnlp_tpu.ops.flash import _interpret

F32 = jnp.float32

#: bytes of one panel ``[K, tn]`` of a group's matrix in fast memory (two
#: matrices' panels, double-buffered, stay under the 16 MiB a kernel may use)
PANEL_BYTES = 2 << 20


def tiles_held(sizes: jax.Array, tile: int) -> jax.Array:
    """``sizes [.., G]`` rows a group, the groups' rows lying one after
    another -> how many row tiles of ``tile`` hold a row of each group."""
    end = jnp.cumsum(sizes, axis=-1)
    start = end - sizes
    return jnp.where(sizes > 0, (end - 1) // tile - start // tile + 1, 0)


def plan(sizes: jax.Array, tile: int, n_tiles: int):
    """``sizes [G]`` rows a group, in order, over ``n_tiles`` row tiles of
    ``tile`` -> (group, tile, first row, end row of each (group, tile) pair
    that holds a row, ``[n_tiles + G - 1]`` int32 each, in the order the
    rows lie; how many pairs there are)."""
    G = sizes.shape[0]
    end = jnp.cumsum(sizes)
    start = end - sizes
    n = tiles_held(sizes, tile)
    pair_end = jnp.cumsum(n)
    v = jnp.arange(n_tiles + G - 1, dtype=jnp.int32)
    g = jnp.minimum(jnp.sum(v[:, None] >= pair_end[None, :], axis=1), G - 1)
    t = start[g] // tile + v - (pair_end - n)[g]
    return (g.astype(jnp.int32), jnp.clip(t, 0, n_tiles - 1).astype(jnp.int32),
            start[g].astype(jnp.int32), end[g].astype(jnp.int32)), pair_end[-1]


def _panel(K: int, N: int, itemsize: int) -> int:
    """Columns of one panel: the most whole lane tiles that divide ``N`` and
    keep ``[K, tn]`` within :data:`PANEL_BYTES`."""
    lanes, cap = N // 128, max(PANEL_BYTES // (K * itemsize) // 128, 1)
    if N % 128 or lanes <= cap:
        return N
    return 128 * max(d for d in range(1, cap + 1) if lanes % d == 0)


def _kernel(g_ref, t_ref, lo_ref, hi_ref, x_ref, *refs, tile, combine):
    """One (group, tile) pair and one panel: ``combine`` of the tile's
    products with the group's panels, written to the group's own rows."""
    *w_refs, o_ref = refs
    p = pl.program_id(1)
    x = x_ref[...]
    y = combine(*(jnp.dot(x, w[...].astype(x.dtype),
                          preferred_element_type=F32) for w in w_refs))
    row = t_ref[p] * tile + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = (row >= lo_ref[p]) & (row < hi_ref[p])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def grouped(x: jax.Array, ws, pairs, n_pairs, base, *, tile: int,
            combine=lambda y: y, out_dtype=F32) -> jax.Array:
    """``combine(x_g @ w[base + g] for w in ws)`` for the rows ``x_g`` of
    every group ``g`` -> ``[R, N]`` ``out_dtype``.  ``x [R, K]`` (``R`` whole
    tiles), ``ws``: stacks ``[G, K, N]`` of one shape, ``pairs`` /
    ``n_pairs``: :func:`plan`'s, ``base``: the stack's group that the plan's
    group 0 is."""
    R, K = x.shape
    N = ws[0].shape[2]
    tn = _panel(K, N, ws[0].dtype.itemsize * len(ws))
    g, t, lo, hi = pairs
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, combine=combine),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, n_pairs),
            in_specs=[pl.BlockSpec((tile, K), lambda n, p, g, t, *_: (t[p], 0))]
            + [pl.BlockSpec((None, K, tn), lambda n, p, g, *_: (g[p], 0, n))
               for _ in ws],
            out_specs=pl.BlockSpec((tile, tn),
                                   lambda n, p, g, t, *_: (t[p], n))),
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(), name="grouped",
    )(g + base, t, lo, hi, x, *ws)
