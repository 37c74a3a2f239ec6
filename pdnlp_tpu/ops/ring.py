"""Ring attention — sequence-parallel attention over a mesh ``seq`` axis.

Long-context training shards the *sequence* across devices; attention then
needs every Q shard to see every KV shard.  Ring attention does this with
``axis_size`` steps of neighbor exchange: each device computes blockwise
attention of its local Q against the KV block it currently holds, folds the
result into an online-softmax accumulator (the same recurrence as the flash
kernel), and passes the KV block to the next device with ``lax.ppermute``
over the ICI ring.  Peak memory per device stays O(S_local) and the
KV transfer overlaps with the block compute under XLA's scheduler.

The reference framework has nothing comparable (max_seq_len fixed at 128,
``SURVEY.md`` §5 "Long-context: absent") — this is a capability the TPU
framework adds, designed mesh-first rather than ported.

Use inside ``shard_map`` with the sequence dimension sharded over
``axis_name`` (see ``parallel.sp`` for the full sequence-parallel encoder).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from pdnlp_tpu.ops.attention import NEG_INF


def _block_attn(q, k, v, bias, drop_key=None, keep=1.0,
                q_seg=None, k_seg=None):
    """One blockwise partial attention: returns (numerator [B,Sq,N,D],
    rowmax m, rowsum l) in fp32 — the merge state of the online softmax.

    ``drop_key`` enables attention-probability dropout for this block: the
    Bernoulli mask multiplies the *numerator* term only (scaled 1/keep),
    while the rowsum ``l`` accumulates the undropped probabilities — so the
    final ``acc / l`` equals ``dropout(softmax(s)) @ v`` exactly, the same
    semantics as the dense path's ``dot_product_attention`` dropout.

    ``q_seg``/``k_seg`` ([B, Sq]/[B, Sk] packed segment IDs, 0 = padding)
    select the PACKED layout: this hop's block-diagonal mask — attend iff
    the local query and the visiting key share a nonzero segment — is
    computed here from the two linear-in-shard ID vectors.  The mask block
    is [B, Sq_local, Sk_local], quadratic in the SHARD width only (the
    same order as the score tensor ``s`` this formulation already holds);
    the global [B, 1, S, S] ``segment_bias`` never exists on any device.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqnd,bknd->bnqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)[:, None, None, :]
    if q_seg is not None:
        same = (q_seg[:, :, None] == k_seg[:, None, :]) & \
            (q_seg[:, :, None] > 0)
        s = s + jnp.where(same, 0.0, NEG_INF)[:, None, :, :]
    m = jnp.max(s, axis=-1, keepdims=True)              # [B,N,Sq,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if drop_key is not None:
        mask = jax.random.bernoulli(drop_key, keep, p.shape)
        p = jnp.where(mask, p / keep, 0.0)
    num = jnp.einsum("bnqk,bknd->bqnd", p, v.astype(jnp.float32))
    return num, m, l


def ring_attention(
    q: jax.Array,                    # [B, S_local, N, D] — this shard's Q
    k: jax.Array,                    # [B, S_local, N, D] — this shard's KV
    v: jax.Array,
    bias_local: Optional[jax.Array],  # [B, S_local] additive mask bias
    axis_name: str = "seq",
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S_local], 0 = padding
) -> jax.Array:
    """Full-sequence attention for a sequence-sharded layout (must run
    inside ``shard_map`` over ``axis_name``).  Output is this shard's rows,
    exactly equal to single-device attention over the gathered sequence.

    ``segment_ids`` selects the PACKED layout (mutually exclusive with
    ``bias_local`` — padding is segment 0): the local shard's IDs stay
    put as the query-side mask input while a copy rotates around the ring
    alongside K/V, and each hop derives its block-diagonal mask from the
    (local, visiting) ID pair — so sequences that span devices compose
    with packing instead of refusing it, and the only mask tensors that
    ever exist are per-hop shard-local blocks (see ``_block_attn``).

    ``dropout_rate``/``dropout_rng`` enable attention-probability dropout
    (the reference BERT's ``attention_probs_dropout_prob``): every (q, kv)
    block pair is visited exactly once around the ring, so an independent
    mask per (shard, ring step) — derived by ``fold_in`` from the caller's
    key — gives each global attention weight one i.i.d. Bernoulli draw.
    Masks depend on the shard layout, so dropped outputs don't match the
    single-device XLA path draw-for-draw (same as any two attention
    backends); the *distribution* is identical (``tests/test_sp.py``)."""
    n = lax.axis_size(axis_name)
    segmented = segment_ids is not None
    if segmented:
        if bias_local is not None:
            raise ValueError("pass bias_local OR segment_ids, not both — "
                             "packed padding is segment 0 and needs no "
                             "separate mask")
        q_seg = segment_ids.astype(jnp.int32)
        extra = q_seg                    # the k-side IDs ride the ring
    else:
        q_seg = None
        extra = (bias_local if bias_local is not None
                 else jnp.zeros(q.shape[:2], jnp.float32))

    dropping = dropout_rate > 0.0 and dropout_rng is not None
    keep = 1.0 - dropout_rate
    base_key = (jax.random.fold_in(dropout_rng, lax.axis_index(axis_name))
                if dropping else None)

    def blk_key(i):
        return jax.random.fold_in(base_key, i) if dropping else None

    def block(k_blk, v_blk, x_blk, key):
        if segmented:
            return _block_attn(q, k_blk, v_blk, None, key, keep,
                               q_seg=q_seg, k_seg=x_blk)
        return _block_attn(q, k_blk, v_blk, x_blk, key, keep)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        acc, m, l, kv = carry
        # rotate first, so exactly n-1 permutes happen across the loop (the
        # local block was consumed before the loop); the transfer overlaps
        # with this step's compute under XLA scheduling
        k_blk, v_blk, x_blk = jax.tree_util.tree_map(
            lambda t: lax.ppermute(t, axis_name, perm), kv)
        num, m_blk, l_blk = block(k_blk, v_blk, x_blk, blk_key(i))
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)                  # rescale old accumulator
        beta = jnp.exp(m_blk - m_new)               # rescale new block
        l = l * alpha + l_blk * beta
        # acc holds [B,Sq,N,D]; alpha/beta are [B,N,Sq,1] -> move axes
        acc = acc * alpha.transpose(0, 2, 1, 3) + num * beta.transpose(0, 2, 1, 3)
        return acc, m_new, l, (k_blk, v_blk, x_blk)

    # step 0: this shard's own KV block, no communication
    acc, m, l = block(k, v, extra, blk_key(0))
    acc, m, l, _ = lax.fori_loop(
        1, n, step, (acc, m, l, (k, v, extra)), unroll=True)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
