"""Decode attention over a paged cache, read where it lies: the step (one
query position a row) of ``models/decoder.paged_attend_layers``.

``q [B, Np, H]`` is the row's EXPANDED query (``decoder._attend_folded``:
head ``n`` in its own ``D``-wide block of a zero row, so one ``H``-wide key
scores every head against its own block); the K and the V pool stay in HBM
as they lie, ``[pages, page_sz, H]`` over all layers, and are never a block
of the grid nor copied.  What says where a row's keys are is data, handed
over as scalars: the row's page ids ``[B, MP]`` (flat, this layer's) and
its LENGTH — ``pos + 1`` for a live row, 0 for a dead one.  The kernel walks
the rows (the grid); for each it copies the pages that hold positions ``<
length`` — and no other: a dead row, a table's sentinel tail and the pages
of the rung past the row's own are never touched — in blocks of ``c`` pages,
two blocks of scratch in turn, the next block's copies (the next live ROW's
first block after a row's last: :func:`plan`) in flight while this one is
scored, and folds each block into a running max / sum / accumulator in
float32 (the online softmax of ``ops/flash.py``).  The last page's rows past
``pos`` belong to nobody (stale, or a later write's): their scores are
masked and their values zeroed, so nothing of them — not a NaN — reaches
the output.  A dead row's output is zeros.

The DMA pattern is ``jax.experimental.pallas.ops.tpu.paged_attention``'s
(copies by page into a double buffer, one semaphore a buffer, the row after
prefetched across the grid step) cut to this pool: one KV head, no
quantized pages, the row after found by a plan made outside instead of a
scan of the lengths inside, and copies of live pages only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pdnlp_tpu.ops.flash import _interpret

F32 = jnp.float32

#: key positions of one block: two lane tiles of scores.  On the chip 256
#: read 14-19 % faster than 128 at the causal cells' rows and lengths (fewer
#: turns of the loop for the same pages); walking several rows a grid step
#: read the same as one (PERF.md, PR 42)
BLOCK = 256
#: what a masked score reads: far below any score, and finite, so a block's
#: max less the running max is never ``inf - inf``
MASKED = -1e30


def plan(lengths: jax.Array) -> jax.Array:
    """``lengths [B]`` -> ``[B + 1]`` int32: entry ``i`` is the first live
    row (length > 0) at or after row ``i``, ``B`` where there is none —
    whose first block a row prefetches after its own last."""
    B = lengths.shape[0]
    rows = jnp.where(lengths > 0, jnp.arange(B, dtype=jnp.int32), B)
    rows = jnp.concatenate([rows, jnp.full((1,), B, jnp.int32)])
    return jax.lax.cummin(rows, reverse=True)


def _kernel(len_ref, nxt_ref, ids_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, done_ref, *, c, scale):
    b, B = pl.program_id(0), pl.num_programs(0)
    ps, H = kbuf.shape[2:]
    MP = ids_ref.shape[0] // B
    bk = c * ps
    length = len_ref[b]

    def block(row, i, slot, go):
        """Start (or wait for) the copies of row ``row``'s block ``i`` into
        buffer ``slot``: its pages that hold a position under the row's
        length — a loop whose bound is data, so a sentinel never reaches a
        copy; the clamps keep a wrong table inside the pool, where Mosaic
        checks nothing."""
        held = pl.cdiv(len_ref[row], ps) - i * c

        def page(j, carry):
            at = jnp.minimum(i * c + j, MP - 1)
            src = jnp.clip(ids_ref[row * MP + at], 0, k_hbm.shape[0] - 1)
            for hbm, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                go(pltpu.make_async_copy(
                    hbm.at[src], buf.at[slot, j], sems.at[s, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(held, c), page, 0)

    start = functools.partial(block, go=lambda copy: copy.start())
    wait = functools.partial(block, go=lambda copy: copy.wait())

    @pl.when(b == 0)
    def _():
        done_ref[0] = 0

        @pl.when(nxt_ref[0] < B)
        def _():
            start(nxt_ref[0], 0, 0)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        done = done_ref[0]      # blocks before this row's: the buffers' turn
        blocks = pl.cdiv(length, bk)
        q = q_ref[...]

        def body(i, carry):
            m, l, acc = carry
            slot = (done + i) % 2

            # in flight while this block is scored: the row's next block,
            # or after its last the next live row's first
            more = i + 1 < blocks
            ahead = jnp.where(more, b, nxt_ref[b + 1])

            @pl.when(ahead < B)
            def _():
                start(ahead, jnp.where(more, i + 1, 0), 1 - slot)

            wait(b, i, slot)
            k = kbuf[slot].reshape(bk, H).astype(q.dtype)
            v = vbuf[slot].reshape(bk, H).astype(q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            seen = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk), 1) < length
            s = jnp.where(seen, s, MASKED)
            top = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - top)              # a masked score's: exact 0.0
            fade = jnp.exp(m - top)
            live = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) < length
            pv = jnp.dot(p.astype(q.dtype), jnp.where(live, v, 0),
                         preferred_element_type=F32)
            return (top, fade * l + p.sum(axis=-1, keepdims=True),
                    fade * acc + pv)

        Np = q.shape[0]
        m, l, acc = jax.lax.fori_loop(
            0, blocks, body,
            (jnp.full((Np, 1), MASKED, F32), jnp.zeros((Np, 1), F32),
             jnp.zeros((Np, H), F32)))
        done_ref[0] = done + blocks
        o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode(q: jax.Array,        # [B, Np, H] expanded queries
                 pool_k: jax.Array,   # [pages, page_sz, H], every layer's
                 pool_v: jax.Array,
                 page_ids: jax.Array,  # [B, MP] int32 rows of the pools
                 lengths: jax.Array,  # [B] int32: keys a row sees, 0 = dead
                 scale: float) -> jax.Array:
    """softmax(``q @ K^T * scale``) ``@ V`` over each row's first
    ``lengths[b]`` key positions, which lie in the pages ``page_ids[b]``
    names, in order -> ``[B, Np, H]`` of ``q``'s dtype.  Jitted, so a
    program that calls it once a layer lowers it once."""
    B, Np, H = q.shape
    ps = pool_k.shape[1]
    c = max(BLOCK // ps, 1)
    lengths = lengths.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, c=c, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, Np, H), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, Np, H), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, c, ps, H), pool_k.dtype),
                            pltpu.VMEM((2, c, ps, H), pool_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(), name="paged_decode",
    )(lengths, plan(lengths), page_ids.astype(jnp.int32).reshape(-1),
      q, pool_k, pool_v)
