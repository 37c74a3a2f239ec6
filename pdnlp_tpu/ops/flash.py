"""Flash attention — Pallas TPU kernel with full custom-VJP backward.

The XLA path (``ops.attention``) materializes the [B, N, S, S] score tensor
in HBM; at seq 128 XLA fuses it well, but the quadratic HBM traffic is what
caps long-context training.  This kernel keeps scores in VMEM tiles and
streams KV blocks through an online softmax (the FlashAttention recurrence),
so HBM traffic stays linear in S.

**Multi-tile structure** (the long-context shape of the kernel): every
kernel runs a 3-D grid whose K/V (or, for dKV, Q) tile index is the
INNERMOST grid dimension, so Pallas's pipeline emitter double-buffers the
streamed 128-wide K/V tiles against the MXU compute — the single-invocation
``fori_loop`` this replaced loaded the whole [S, D] K/V into VMEM up front
(no fetch/compute overlap, VMEM linear in S).  The fp32 accumulators
(output numerator, running rowmax ``m``, running rowsum ``l``) live in VMEM
scratch across the inner iterations and are written back exactly once:

- **forward**: grid (B*N, S/128 Q tiles, S/128 KV tiles); saves the (m, l)
  rows for the backward pass.  The rows are saved SEPARATELY, not folded
  into the usual logsumexp ``L = m + log l``: a fully-masked query row
  (packed-row padding is segment 0) puts every score at ``-1e9``, where
  fp32 resolution is ~64 — the ``log l`` term would round away entirely and
  the backward's recomputed probabilities would come back unnormalized.
  ``exp(s - m) / l`` is exact there, matching XLA's softmax VJP.
- **backward**: two independent kernels (no cross-grid accumulation):
  dQ gridded (B*N, Q tiles, KV tiles), dK/dV gridded (B*N, KV tiles,
  Q tiles), both recomputing probabilities from (m, l) — the standard
  FlashAttention-2 split.

**Block-sparse tile skip**: every kernel consumes a tiny per-(batch,
q-tile, k-tile) activity map (linear-in-S to build, ``(S/128)^2`` int32s —
never the [S, S] bias) and wraps the tile compute in ``pl.when``:

- packed rows (:func:`segment_block_map`): a tile is live iff the q tile's
  and k tile's nonzero-segment-ID ranges intersect.  Packed rows are
  block-diagonal, so off-diagonal tiles — the asymptotic majority at
  512-8k widths — skip their matmuls entirely.  Skipping is EXACT, not
  approximate: a skipped tile's probabilities are ``exp(raw - 1e9 - m)``,
  which underflows fp32 to literal 0.0 for any query row with at least one
  live tile.  A q tile containing padding rows (segment 0) stays fully
  live — a fully-masked row's output is softmax of the raw scores (both
  impls' documented semantics), which needs every tile.
- dense masks (:func:`bias_block_map`): a k tile whose additive bias is
  uniformly ``-1e9`` (padding beyond the batch's real tokens) is skipped
  for the whole batch row, unless the row is ALL masked (filler rows keep
  every tile so the softmax-of-raw semantics hold).  Long padded rows are
  mostly padding, so the dense path sheds its padding tiles too.

**Segment-native masking** (``segment_ids``): packed rows
(``data.packing``) need a block-diagonal mask so co-packed examples never
cross-attend.  The XLA path materializes it as a [B, 1, S, S] additive
``segment_bias`` in HBM; here the mask is computed *inside the kernel* from
per-token segment IDs held in VMEM — the [S, S] bias never exists.  The
IDs travel in two linear-in-S layouts (the splash-attention convention, so
no sublane<->lane relayout happens in-kernel):

- k-side: ``[B, 1, S]`` int32, read as a lane row;
- q-side: ``[B, S, LANES]`` int32 (IDs broadcast over a 128-lane minor
  dim), read as a ``[block, 1]`` column slice.

The mask is applied ADDITIVELY (0 / -1e9), bit-matching the XLA
``segment_bias`` semantics — including on fully-padded query rows, where
both formulations reduce to softmax of the raw scores.

All matmuls run on the MXU with fp32 accumulation (``preferred_element_type``)
regardless of the compute dtype.  Probability dropout is not implemented —
``ops.attention`` routes training-with-attn-dropout to the XLA path.

Capability note: the reference framework has no custom kernels (its native
ops live in cuDNN/NCCL, ``SURVEY.md`` §2.4); this is the owned-TPU-kernel
equivalent and the building block of the long-context path (``ops.ring``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
LANES = 128   # minor-dim width of the q-side segment-ID layout
assert BLOCK_K == LANES  # the lane-broadcast (m, l) scratch relies on it
NEG_INF = -1e9


def _interpret() -> bool:
    """Pallas TPU kernels run via the interpreter on non-TPU backends (CI's
    virtual CPU mesh); compiled Mosaic on real chips."""
    return jax.default_backend() != "tpu"


def _compiler_params():
    """Grid dimension semantics: (batch*head, q-tile) iterate freely; the
    innermost streamed tile axis is sequential (it owns the scratch
    accumulators).  Interpret mode ignores the hint."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _tile_live(act_ref, n_heads, n_q, n_k, qi, ki):
    """This grid step's entry of the tile-activity map.  The map rides the
    grid as a scalar-prefetch operand: the whole ``[B * n_q * n_k]`` int32
    vector sits in SMEM before the body runs (Mosaic refuses a ``(1, 1, 1)``
    VMEM block over it at any width above one tile), flattened to 1-D
    because SMEM pads every minor dim of a multi-dim array to a full tile."""
    b = pl.program_id(0) // n_heads
    return act_ref[(b * n_q + qi) * n_k + ki] != 0


def supported_seq(seq_len: int) -> bool:
    """Static-shape gate: S must tile by the 128-wide kernel blocks."""
    return seq_len >= BLOCK_Q and seq_len % BLOCK_Q == 0


def supported(q: jax.Array) -> bool:
    """Static-shape gate used by ``ops.attention`` (``q``: [B, S, N, D])."""
    return supported_seq(q.shape[1])


# ------------------------------------------------------------- block maps


def segment_block_map(segment_ids: jax.Array) -> jax.Array:
    """[B, S] segment IDs -> [B, S/128, S/128] int32 tile-activity map.

    A (q-tile, k-tile) pair is live iff the tiles' nonzero segment-ID
    ranges intersect (packed segments are contiguous, so the min/max range
    test is exact for them and merely conservative for any other ID
    layout), OR the q tile contains padding rows (segment 0) — a
    fully-masked row's output is softmax of the raw scores, which needs
    every tile (see the module docstring: skipping is exact only for rows
    with a live tile).  Linear in S to build, ``(S/128)^2`` int32s per
    batch row — the [B, 1, S, S] bias never exists anywhere.
    """
    seg = jnp.asarray(segment_ids, jnp.int32)
    B, S = seg.shape
    qb = seg.reshape(B, S // BLOCK_Q, BLOCK_Q)
    kb = seg.reshape(B, S // BLOCK_K, BLOCK_K)
    big = jnp.int32(2 ** 30)
    qmin = jnp.min(jnp.where(qb > 0, qb, big), -1)   # [B, nq]
    qmax = jnp.max(qb, -1)                           # padding (0) < any id
    kmin = jnp.min(jnp.where(kb > 0, kb, big), -1)
    kmax = jnp.max(kb, -1)
    has_pad_q = jnp.any(qb == 0, -1)                 # [B, nq]
    inter = ((qmin[:, :, None] <= kmax[:, None, :])
             & (kmin[:, None, :] <= qmax[:, :, None]))
    return (inter | has_pad_q[:, :, None]).astype(jnp.int32)


def bias_block_map(bias2: jax.Array, n_q: int) -> jax.Array:
    """[B, 1, S] additive mask bias -> [B, n_q, S/128] tile-activity map.

    A k tile is dead when its bias is uniformly at the ``-1e9`` floor
    (padding keys shared by every query row — the bias is per-key).  A
    batch row whose EVERY key is masked (zero-weight filler rows) keeps
    all tiles so its softmax-of-raw output matches the XLA path exactly.
    """
    B = bias2.shape[0]
    S = bias2.shape[-1]
    kb = bias2.reshape(B, S // BLOCK_K, BLOCK_K)
    act_k = jnp.any(kb > NEG_INF / 2, -1)            # [B, nk]
    all_masked = ~jnp.any(act_k, -1)                 # [B]
    act = act_k | all_masked[:, None]
    return jnp.broadcast_to(act[:, None, :],
                            (B, n_q, act.shape[-1])).astype(jnp.int32)


def _seg_inputs(segment_ids: jax.Array):
    """[B, S] segment IDs -> (k-side [B, 1, S], q-side [B, S, LANES]).

    Both are linear in S (int32), vs the quadratic [B, 1, S, S] bias the
    XLA path materializes.  The q-side lane broadcast exists so the kernel
    can read a [block, 1] COLUMN of IDs without a lane->sublane relayout;
    XLA CSEs the broadcast across the (fully unrolled) layer stack, so it
    is built once per step, not once per layer.
    """
    seg = segment_ids.astype(jnp.int32)
    seg_kv = seg[:, None, :]
    seg_q = jnp.broadcast_to(seg[:, :, None], seg.shape + (LANES,))
    return seg_kv, seg_q


def _seg_bias_block(qs, ks):
    """Additive mask block from ID slices (qs: [rows, 1], ks: [1, cols]):
    0 where query and key share a nonzero segment, -1e9 elsewhere —
    exactly ``data.packing.segment_bias`` semantics, computed in VMEM."""
    same = (qs == ks) & (qs > 0)
    return jnp.where(same, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------- forward


def _fwd_kernel(act_ref, *refs, scale, n_heads, n_q, n_k, segmented):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref,
         o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr) = refs
    else:
        (q_ref, k_ref, v_ref, bias_ref,
         o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr) = refs
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(_tile_live(act_ref, n_heads, n_q, n_k, qi, ki))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if segmented:
            s = s + _seg_bias_block(sq_ref[0, :, :1], skv_ref[0, 0][None, :])
        else:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        # (m, l) scratch is lane-broadcast [Bq, LANES] (every lane equal),
        # so s [Bq, BLOCK_K == LANES] composes elementwise with no relayout
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        # (m, l) saved separately — see module docstring: folding them into
        # L = m + log(l) loses log(l) to fp32 rounding on fully-masked rows
        m_ref[0, 0] = m_scr[...][:, 0]
        l_ref[0, 0] = l[:, 0]


def _block_specs(D, n_heads, segmented, at=lambda i, j: (i, j)):
    """The operands' BlockSpecs for one grid order: ``at`` maps the two
    inner grid indices to (q tile, k tile) — identity for the forward and
    dQ grids, swapped for dK/dV.  Mask operands live at batch granularity
    and are broadcast over heads via ``bh // n_heads`` — no N-fold HBM copy.
    The tile map is scalar-prefetched (:func:`_tile_live`), so every index
    map takes its ref as a trailing argument.  -> (q, kv, row, [mask...])."""
    n = n_heads

    def spec(block, index):
        def index_map(bh, i, j, act_ref):
            return index(bh, *at(i, j))
        return pl.BlockSpec(block, index_map)

    q_spec = spec((1, BLOCK_Q, D), lambda bh, qi, ki: (bh, qi, 0))
    kv_spec = spec((1, BLOCK_K, D), lambda bh, qi, ki: (bh, ki, 0))
    row_spec = spec((1, 1, BLOCK_Q), lambda bh, qi, ki: (bh, 0, qi))
    key_spec = spec((1, 1, BLOCK_K), lambda bh, qi, ki: (bh // n, 0, ki))
    if segmented:   # (seg_q, seg_kv) operand order
        mask_specs = [spec((1, BLOCK_Q, LANES),
                           lambda bh, qi, ki: (bh // n, qi, 0)), key_spec]
    else:
        mask_specs = [key_spec]
    return q_spec, kv_spec, row_spec, mask_specs


def _mask_operands(mask, segmented):
    """mask: [B,1,S] bias, or (seg_kv, seg_q) -> operands in spec order."""
    if segmented:
        seg_kv, seg_q = mask
        return [seg_q, seg_kv]
    return [mask]


def _fwd(q3, k3, v3, mask, active, scale, n_heads, segmented):
    """q3/k3/v3: [BN, S, D]; mask: [B,1,S] bias or (seg_kv, seg_q);
    active: [B, nq, nk] tile map.  -> (o3, m[BN, 1, S], l[BN, 1, S])."""
    BN, S, D = q3.shape
    nq, nk = S // BLOCK_Q, S // BLOCK_K
    kernel = functools.partial(_fwd_kernel, scale=scale, n_heads=n_heads,
                               n_q=nq, n_k=nk, segmented=segmented)
    q_spec, kv_spec, row_spec, mask_specs = _block_specs(D, n_heads,
                                                         segmented)
    o3, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BN, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, *mask_specs],
            out_specs=[q_spec, row_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((BLOCK_Q, D), jnp.float32),
                pltpu.VMEM((BLOCK_Q, LANES), jnp.float32),
                pltpu.VMEM((BLOCK_Q, LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BN, S, D), q3.dtype),
            jax.ShapeDtypeStruct((BN, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((BN, 1, S), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(active.reshape(-1), q3, k3, v3, *_mask_operands(mask, segmented))
    return o3, m, l


# --------------------------------------------------------------- backward


def _dq_kernel(act_ref, *refs, scale, n_heads, n_q, n_k, segmented):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref,
         m_ref, l_ref, Di_ref, dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, bias_ref, do_ref,
         m_ref, l_ref, Di_ref, dq_ref, dq_scr) = refs
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_tile_live(act_ref, n_heads, n_q, n_k, qi, ki))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                 # [Bq, D]
        m = m_ref[0, 0][:, None]                           # [Bq, 1]
        l = l_ref[0, 0][:, None]
        Di = Di_ref[0, 0][:, None]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if segmented:
            s = s + _seg_bias_block(sq_ref[0, :, :1], skv_ref[0, 0][None, :])
        else:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        p = jnp.exp(s - m) / l                             # [Bq, Bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - Di)
        dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(act_ref, *refs, scale, n_heads, n_q, n_k, segmented):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref,
         m_ref, l_ref, Di_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, bias_ref, do_ref,
         m_ref, l_ref, Di_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_live(act_ref, n_heads, n_q, n_k, qi, ki))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # [Bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                 # [Bq, D]
        m = m_ref[0, 0][:, None]                           # [Bq, 1]
        l = l_ref[0, 0][:, None]
        Di = Di_ref[0, 0][:, None]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if segmented:
            s = s + _seg_bias_block(sq_ref[0, :, :1], skv_ref[0, 0][None, :])
        else:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        p = jnp.exp(s - m) / l                             # [Bq, Bk]
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - Di)                                 # [Bq, Bk]
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl(scale, n_heads, segmented, res, do3):
    q3, k3, v3, mask, active, o3, m, l = res
    BN, S, D = q3.shape
    nq, nk = S // BLOCK_Q, S // BLOCK_K
    Di = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                 axis=-1)[:, None, :]
    operands = (active.reshape(-1), q3, k3, v3,
                *_mask_operands(mask, segmented), do3, m, l, Di)
    static = dict(scale=scale, n_heads=n_heads, n_q=nq, n_k=nk,
                  segmented=segmented)

    def in_specs(q_spec, kv_spec, row_spec, mask_specs):
        return [q_spec, kv_spec, kv_spec, *mask_specs,
                q_spec, row_spec, row_spec, row_spec]

    specs = _block_specs(D, n_heads, segmented)
    dq3 = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BN, nq, nk),
            in_specs=in_specs(*specs),
            out_specs=specs[0],
            scratch_shapes=[pltpu.VMEM((BLOCK_Q, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BN, S, D), q3.dtype),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*operands)

    # dK/dV: k tiles outer, q tiles innermost
    specs = _block_specs(D, n_heads, segmented, at=lambda ki, qi: (qi, ki))
    dk3, dv3 = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BN, nk, nq),
            in_specs=in_specs(*specs),
            out_specs=[specs[1], specs[1]],
            scratch_shapes=[
                pltpu.VMEM((BLOCK_K, D), jnp.float32),
                pltpu.VMEM((BLOCK_K, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BN, S, D), k3.dtype),
            jax.ShapeDtypeStruct((BN, S, D), v3.dtype),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*operands)
    return dq3, dk3, dv3


# ---------------------------------------------------- custom-VJP wrappers


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash3(q3, k3, v3, bias2, active, scale, n_heads):
    """bias2: [B, 1, S] additive, broadcast over heads via the index map;
    active: [B, nq, nk] tile map (``bias_block_map``)."""
    return _fwd(q3, k3, v3, bias2, active, scale, n_heads,
                segmented=False)[0]


def _flash3_fwd(q3, k3, v3, bias2, active, scale, n_heads):
    o3, m, l = _fwd(q3, k3, v3, bias2, active, scale, n_heads,
                    segmented=False)
    return o3, (q3, k3, v3, bias2, active, o3, m, l)


def _flash3_bwd(scale, n_heads, res, do3):
    return _bwd_impl(scale, n_heads, False, res, do3) + (None, None)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _flash3_seg(q3, k3, v3, seg_kv, seg_q, active, scale, n_heads):
    """Segment-native variant: the block-diagonal mask is computed inside
    the kernels from (seg_kv [B,1,S], seg_q [B,S,LANES]) int32 IDs, and
    ``active`` (``segment_block_map``) skips the dead off-diagonal tiles."""
    return _fwd(q3, k3, v3, (seg_kv, seg_q), active, scale, n_heads,
                segmented=True)[0]


def _flash3_seg_fwd(q3, k3, v3, seg_kv, seg_q, active, scale, n_heads):
    o3, m, l = _fwd(q3, k3, v3, (seg_kv, seg_q), active, scale, n_heads,
                    segmented=True)
    return o3, (q3, k3, v3, (seg_kv, seg_q), active, o3, m, l)


def _flash3_seg_bwd(scale, n_heads, res, do3):
    return _bwd_impl(scale, n_heads, True, res, do3) + (None, None, None)


_flash3_seg.defvjp(_flash3_seg_fwd, _flash3_seg_bwd)


def flash_attention(
    q: jax.Array,   # [B, S, N, D]
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,  # [B, 1, 1, S] additive (mask_bias)
    segment_ids: Optional[jax.Array] = None,  # [B, S] int, 0 = padding
) -> jax.Array:
    """Drop-in for the XLA path of ``ops.attention.dot_product_attention``
    (same [B, S, N, D] layout, same additive-bias contract).

    ``segment_ids`` selects the segment-native packed path: the
    block-diagonal mask (``data.packing.segment_bias`` semantics — attend
    iff query and key share a nonzero segment) is derived in-kernel from
    the IDs, so the [B, 1, S, S] bias never materializes in HBM, and the
    off-diagonal tiles the mask kills are skipped outright
    (``segment_block_map``).  Mutually exclusive with ``bias`` — padding
    is already segment 0.
    """
    B, S, N, D = q.shape
    scale = D ** -0.5

    def to3(t):
        return t.transpose(0, 2, 1, 3).reshape(B * N, S, D)

    if segment_ids is not None:
        if bias is not None:
            raise ValueError("pass bias OR segment_ids, not both — padding "
                             "is segment 0 and needs no separate mask")
        seg_kv, seg_q = _seg_inputs(segment_ids)
        active = segment_block_map(segment_ids)
        o3 = _flash3_seg(to3(q), to3(k), to3(v), seg_kv, seg_q, active,
                         scale, N)
        return o3.reshape(B, N, S, D).transpose(0, 2, 1, 3)
    if bias is None:
        bias2 = jnp.zeros((B, 1, S), jnp.float32)
    else:
        bias2 = bias.reshape(B, 1, S).astype(jnp.float32)
    active = bias_block_map(bias2, S // BLOCK_Q)
    o3 = _flash3(to3(q), to3(k), to3(v), bias2, active, scale, N)
    return o3.reshape(B, N, S, D).transpose(0, 2, 1, 3)
