"""Deterministic distributed sharding of the dataset — the
``DistributedSampler`` analog.

The reference relies on ``torch.utils.data.DistributedSampler`` to give each
rank a disjoint 1/world_size slice of an epoch-seeded permutation, padding so
every rank sees the same number of steps
(``multi-gpu-distributed-cls.py:314-330``; ``set_epoch`` at ``:164``).

On TPU the "rank" is the host process: each host materializes only its shard
of the global batch and the arrays are assembled into one global-sharded
``jax.Array`` (see ``parallel.collectives.make_global_batch``).  Indices pad
by wrapping, like the reference's sampler, so step counts match (144 steps at
2-way DP for the 9,200-example epoch, ``SURVEY.md`` §6).

Elastic-width contract: every epoch order is a pure function of
``(seed, epoch)`` and row assignment a pure function of
``(num_shards, shard_id)`` over it — nothing is cached across widths — so
a gang that resumes at a DIFFERENT data-parallel width (a dead host
evicted, ``parallel/watchdog.GangSupervisor``) recomputes row assignment
correctly just by being rebuilt at the new width.  Same-width resume
replays the identical stream (bitwise continuation); across widths the
consumed-example SET is only approximately the old prefix (the interleave
changes), which is why ``Trainer._remap_elastic_width`` continues by epoch
fraction and documents the few-rows skip.
"""
from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import numpy as np


class DistributedShardSampler:
    def __init__(
        self,
        num_examples: int,
        num_shards: int = 1,
        shard_id: int = 0,
        shuffle: bool = True,
        seed: int = 123,
        drop_last: bool = False,
    ):
        assert 0 <= shard_id < num_shards
        self.num_examples = num_examples
        self.num_shards = num_shards
        self.shard_id = shard_id
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        if drop_last:
            self.shard_len = num_examples // num_shards
        else:
            self.shard_len = -(-num_examples // num_shards)  # ceil

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle differently each epoch (DistributedSampler.set_epoch analog)."""
        self.epoch = epoch

    def global_order(self) -> np.ndarray:
        if self.shuffle:
            rng = np.random.RandomState(self.seed + self.epoch)
            return rng.permutation(self.num_examples)
        return np.arange(self.num_examples)

    def shard_indices(self) -> np.ndarray:
        """This shard's indices: strided slice of the (padded) global order."""
        order = self.global_order()
        total = self.shard_len * self.num_shards
        if total > len(order):  # pad by wrapping, like DistributedSampler
            order = np.concatenate([order, order[: total - len(order)]])
        else:
            order = order[:total]
        return order[self.shard_id :: self.num_shards]

    def __iter__(self) -> Iterator[int]:
        return iter(self.shard_indices().tolist())

    def __len__(self) -> int:
        return self.shard_len


# --------------------------------------------------------------------------
# length-aware batching (--length_mode)
# --------------------------------------------------------------------------

def parse_buckets(spec: str, max_seq_len: int) -> Tuple[int, ...]:
    """``"32,64,128"`` -> sorted bucket widths, clipped to ``max_seq_len``.

    Widths over ``max_seq_len`` are dropped (the encoding truncates there —
    a wider bucket could never fill) and ``max_seq_len`` itself is always
    the last bucket, so every example has a covering bucket."""
    try:
        widths = {int(w) for w in str(spec).split(",") if str(w).strip()}
    except ValueError:
        raise ValueError(f"--length_buckets must be comma-separated ints, "
                         f"got {spec!r}")
    if any(w < 2 for w in widths):
        raise ValueError(f"bucket widths must be >= 2 ([CLS]+[SEP]), "
                         f"got {sorted(widths)}")
    return tuple(sorted(w for w in widths if w < max_seq_len)) + (max_seq_len,)


def validate_length_buckets(widths: Sequence[int], *, max_position: int,
                            model: str, mode: str = "bucket",
                            max_seq_len: int = None) -> None:
    """SETUP-time position-table validation of ``--length_buckets``.

    Position embeddings are a gather into the model's ``[max_position, H]``
    table, and JAX clamps out-of-bounds gathers instead of raising — an
    unpacked 1024-wide bucket on bert-base (512 positions) would silently
    train on garbage embeddings for every position past 511.  Loudly
    refuse at setup instead, with the fix named.

    - ``mode="bucket"`` (unpacked rows, positions 0..width-1): every
      bucket width must fit the table;
    - ``mode="pack"`` (packed rows, positions restart per segment): the
      bound is the longest possible SEGMENT — the encode width
      (``max_seq_len``) — so pack widths may legitimately exceed the
      table (a 2048-wide packed row of <=512-token documents is exactly
      the long-context payoff).
    """
    if mode == "bucket":
        bad = sorted(int(w) for w in widths if int(w) > int(max_position))
        if bad:
            raise ValueError(
                f"--length_buckets includes {bad} but {model}'s position "
                f"table has only {max_position} positions — an unpacked "
                f"{bad[0]}-wide batch would gather position embeddings "
                "past the table (JAX clamps the gather: silent garbage, "
                "no error).  Use a long-position model (--model "
                "bert-base-long has 2048 positions) or drop the bucket")
    elif max_seq_len is not None and int(max_seq_len) > int(max_position):
        raise ValueError(
            f"--length_mode pack with --max_seq_len {max_seq_len} exceeds "
            f"{model}'s {max_position}-position table — packed positions "
            "restart per segment, so the bound is the longest segment "
            "(= the encode width), and a longer one would silently gather "
            "garbage position embeddings.  Lower --max_seq_len or use a "
            "long-position model (--model bert-base-long)")


def resolve_length_mode(args) -> str:
    """The ``--length_mode`` decision, in one place.

    ``auto`` resolves to ``full``: bucket/pack keep per-example math intact
    but change batch COMPOSITION (which examples co-occur in a step), so
    every committed loss trace and golden run stays reference-exact unless
    a run opts in (what opting in buys: PERF.md, the ``finetune-pack128``
    cell still to add)."""
    mode = getattr(args, "length_mode", "auto") or "auto"
    if mode not in ("auto", "full", "bucket", "pack"):
        raise ValueError(f"unknown length_mode {mode!r}; use "
                         "auto|full|bucket|pack")
    return "full" if mode == "auto" else mode


class LengthGroupedSampler:
    """Seeded length-grouped batching: bucket-homogeneous batches that
    still shard deterministically across processes.

    Every process computes the SAME global batch sequence from the seed —
    per epoch, examples are permuted within their length bucket, chopped
    into global batches of ``batch_size * num_shards``, and the epoch
    visits the buckets as contiguous BLOCKS in a seeded order — then takes
    its strided slice of each global batch.  Three consequences the
    trainer and pipelines rely on:

    - at any global step every process feeds the same bucket (the SPMD
      global batch stays shape-consistent across hosts);
    - within a bucket block every batch shares one shape, so
      ``fuse_steps``-sized fusion groups are shape-homogeneous by
      construction and the compile count stays bounded at
      ``len(buckets) x len(step-variants)``, never per-batch;
    - the epoch's RUN STRUCTURE (batches per bucket, fused groups per
      bucket) is epoch-invariant — bucket membership is a function of the
      data, only the order within and across blocks reshuffles — so the
      device-resident pipeline's per-bucket gather programs and the step
      programs compile on epoch one and never re-trace, and resume
      fast-forward by step count stays exact.

    Determinism note: length-grouping changes which examples CO-OCCUR in
    a batch (and bucket-blocking makes batch order length-correlated
    within an epoch); it never changes any example's own tokens, mask, or
    loss weight.  The last batch of each bucket may be short; the loader
    pads it with the usual zero-weight filler.
    """

    def __init__(
        self,
        lengths: Sequence[int],
        batch_size: int,
        buckets: Sequence[int] = (32, 64, 128),
        num_shards: int = 1,
        shard_id: int = 0,
        shuffle: bool = True,
        seed: int = 123,
        drop_last: bool = False,
    ):
        assert 0 <= shard_id < num_shards
        self.lengths = np.asarray(lengths, np.int64)
        self.num_examples = len(self.lengths)
        self.batch_size = int(batch_size)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.num_shards = num_shards
        self.shard_id = shard_id
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        # bucket membership is epoch-invariant: smallest covering width
        # (over-long examples land in the last bucket — the encoding
        # truncates to max_seq_len there, same longest-first outcome)
        edges = np.asarray(self.buckets, np.int64)
        self._member = edges[np.minimum(
            np.searchsorted(edges, self.lengths), len(edges) - 1)]
        G = self.batch_size * self.num_shards
        self.batches_per_epoch = 0
        for b in self.buckets:
            n = int((self._member == b).sum())
            self.batches_per_epoch += (n // G if drop_last
                                       else -(-n // G)) if n else 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def global_batches(self) -> List[Tuple[np.ndarray, int]]:
        """This epoch's ``(global_indices, bucket)`` sequence — identical
        on every process (seeded).  Buckets come as contiguous blocks (a
        bucket's short tail batch last in its block) in a seeded block
        order; see the class docstring for why the structure must be
        epoch-invariant."""
        rng = np.random.RandomState(self.seed + self.epoch)
        G = self.batch_size * self.num_shards
        blocks: List[List[Tuple[np.ndarray, int]]] = []
        for b in self.buckets:  # ascending: deterministic rng consumption
            idx = np.flatnonzero(self._member == b)
            if not len(idx):
                continue
            if self.shuffle:
                idx = idx[rng.permutation(len(idx))]
            chunks = [(idx[i: i + G], int(b)) for i in range(0, len(idx), G)]
            if self.drop_last and len(chunks) and len(chunks[-1][0]) < G:
                chunks.pop()
            if chunks:
                blocks.append(chunks)
        if self.shuffle:
            blocks = [blocks[i] for i in rng.permutation(len(blocks))]
        return [c for block in blocks for c in block]

    def chunks(self) -> Iterator[Tuple[List[int], int]]:
        """Yield ``(local_indices, bucket)`` per batch: this shard's
        strided slice of each global batch (rows, not batches, shard —
        every process sees every step, in the same bucket)."""
        for gidx, bucket in self.global_batches():
            yield gidx[self.shard_id:: self.num_shards].tolist(), bucket

    def __iter__(self) -> Iterator[int]:
        for chunk, _bucket in self.chunks():
            yield from chunk

    def __len__(self) -> int:
        # examples this shard feeds per epoch (loader __len__ uses
        # batches_per_epoch for the step count instead) — arithmetic over
        # the epoch-invariant bucket membership, no epoch materialization:
        # a full global batch slices to exactly batch_size rows per shard;
        # a tail of t rows slices to |{i in [0,t): i ≡ shard_id (mod S)}|
        G = self.batch_size * self.num_shards
        total = 0
        for b in self.buckets:
            n = int((self._member == b).sum())
            full, tail = divmod(n, G)
            total += full * self.batch_size
            if not self.drop_last and tail > self.shard_id:
                total += -(-(tail - self.shard_id) // self.num_shards)
        return total
