"""Sequence packing for MLM pretraining — fill every row, waste no MXU.

The corpus texts average ~18 tokens (`data/train.json`), so padding each to
`max_seq_len=128` would burn ~85% of the FLOPs on [PAD].  TPU-natively the
fix is *packing*: concatenate `[CLS] text [SEP]` segments back-to-back into
fixed `[N, S]` rows and carry a `segment_ids` channel; attention uses a
block-diagonal bias (`segment_bias`) so tokens never attend across text
boundaries, while every position in the row still trains the full 0..S-1
position-embedding table.  This has no reference twin — the reference never
pretrains (`/root/reference/single-gpu-cls.py:252-255` downloads pretrained
weights; this environment has no egress, so pretraining is built instead).

Shapes stay fully static: one (num_rows, S) int32 array per channel.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from pdnlp_tpu.data.collate import EncodedDataset
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer


def pack_texts(
    tok: WordPieceTokenizer,
    texts: Sequence[str],
    max_seq_len: int = 128,
) -> Dict[str, np.ndarray]:
    """Greedy first-fit packing of tokenized texts into `[N, S]` rows.

    Returns `{"input_ids", "segment_ids"}`; `segment_ids` is 1-based per
    text within a row, 0 = padding.  A text longer than `S-2` tokens is
    truncated (same `longest_first` outcome as the fine-tune collator).
    """
    S = max_seq_len
    rows: List[List[int]] = []
    segs: List[List[int]] = []
    for text in texts:
        ids = tok.encode_ids(text, S)
        if not rows or len(rows[-1]) + len(ids) > S:
            rows.append([])
            segs.append([])
        seg = (segs[-1][-1] + 1) if segs[-1] else 1
        rows[-1].extend(ids)
        segs[-1].extend([seg] * len(ids))
    n = len(rows)
    input_ids = np.zeros((n, S), np.int32)
    segment_ids = np.zeros((n, S), np.int32)
    for i, (r, s) in enumerate(zip(rows, segs)):
        input_ids[i, : len(r)] = r
        segment_ids[i, : len(s)] = s
    return {"input_ids": input_ids, "segment_ids": segment_ids}


class _BfdPacker:
    """Best-fit-decreasing placement core: feed items longest first; each
    goes to the open row with the LEAST free space that still fits it —
    O(n log n) via a bisect-sorted (free, row) list; a row at the segment
    cap closes.  Deterministic: ties break on row id (stable tuple
    order).  ONE copy of the placement invariants, shared by the
    single-width packer and the multi-width seed/backfill passes."""

    def __init__(self, S: int, M: int):
        self.S, self.M = int(S), int(M)
        self.rows: List[List[int]] = []
        self._open: List[tuple] = []  # sorted (free_tokens, row_id)

    @property
    def has_open(self) -> bool:
        return bool(self._open)

    def place(self, i: int, L: int, open_new: bool = True) -> bool:
        """Place item ``i`` of ``L`` tokens; ``open_new=False`` restricts
        to existing open rows (the backfill pass never opens rows)."""
        j = bisect.bisect_left(self._open, (L, -1))
        if j < len(self._open):
            free, rid = self._open.pop(j)
            self.rows[rid].append(i)
            if len(self.rows[rid]) < self.M and free - L > 0:
                bisect.insort(self._open, (free - L, rid))
            return True
        if not open_new:
            return False
        self.rows.append([i])
        if self.M > 1 and self.S - L > 0:
            bisect.insort(self._open, (self.S - L, len(self.rows) - 1))
        return True


def _bfd_rows(lengths: np.ndarray, S: int, M: int) -> List[List[int]]:
    """Pack every item (longest first) via :class:`_BfdPacker`; returns
    rows of POSITIONS into ``lengths``."""
    packer = _BfdPacker(S, M)
    for i in np.argsort(-np.asarray(lengths), kind="stable").tolist():
        packer.place(i, int(lengths[i]))
    return packer.rows


def segment_cap(width: int, base_cap: int, base_width: int = 128) -> int:
    """Per-width segment capacity: ``--pack_max_segments`` is defined at
    the base (128-token, one kernel block) width and scales linearly with
    the row width, so a 512-wide packed row admits 4x the segments a
    128-wide one does — same expected density, per-width ``[N, M]``
    channel shapes stay bounded."""
    return max(1, int(base_cap) * int(width) // int(base_width))


class PackedClassificationDataset(EncodedDataset):
    """Classification examples packed many-per-row — the fine-tune twin of
    :func:`pack_texts` (``--length_mode pack``).

    Quacks like :class:`~pdnlp_tpu.data.collate.EncodedDataset` (``arrays``
    / ``take`` / ``lengths``), so the loader, the device-resident pipeline,
    and the HBM-budget check all work unchanged — the unit simply becomes a
    packed ROW instead of an example.  Channels per row (all static):

    - ``input_ids`` ``[N, S]``: ``[CLS] text [SEP]`` segments back-to-back;
    - ``segment_ids`` ``[N, S]``: 1-based per segment, 0 = padding — feeds
      the block-diagonal ``segment_bias`` so examples never cross-attend;
    - ``attention_mask`` ``[N, S]``: ``segment_ids > 0``;
    - ``cls_positions`` ``[N, M]``: each segment's [CLS] token offset (the
      per-segment pooled-output gather in ``models.bert``);
    - ``label`` / ``example_weight`` ``[N, M]``: per-SEGMENT targets and
      weights (0 = empty slot), so the loss stays per-example, not per-row.

    ``width`` overrides the row width (default: the encoding width) —
    the multi-width path (:class:`MultiWidthPackedDataset`) packs each
    length bucket at its own kernel-tiling width.  ``subset`` restricts
    packing to those encoded-example indices (the bucket's members);
    labels and tokens are still read from the full encoded split.
    ``rows`` (lists of encoded-example indices) bypasses the packer and
    assembles exactly those rows — the multi-width container computes
    its own backfilled packing and hands the rows here for assembly.

    Packing is computed ONCE (best-fit-decreasing, seeded by nothing —
    deterministic in the data): epochs shuffle packed *rows*, keeping the
    per-epoch step count and resume arithmetic exact.  What changes vs the
    host loader is batch composition only — which examples co-occur — never
    any example's own tokens, mask, or loss weight.
    """

    def __init__(self, encoded: EncodedDataset, max_segments: int = 16,
                 width: Optional[int] = None,
                 subset: Optional[Sequence[int]] = None,
                 rows: Optional[List[List[int]]] = None):
        S = int(width) if width else encoded.seq_len
        M = int(max_segments)
        if M < 1:
            raise ValueError(f"pack_max_segments must be >= 1, got {M}")
        all_len = encoded.lengths()
        if rows is None:
            members_idx = (np.arange(len(encoded), dtype=np.int64)
                           if subset is None
                           else np.asarray(subset, np.int64))
            lengths = all_len[members_idx]
            if len(members_idx) and int(lengths.max()) > S:
                raise ValueError(
                    f"cannot pack a {int(lengths.max())}-token example "
                    f"into {S}-wide rows — the packing width must cover "
                    "every member (partition by covering width first)")
            rows_pos = _bfd_rows(lengths, S, M)
            rows = [[int(members_idx[i]) for i in r] for r in rows_pos]
            n = len(members_idx)
        else:
            rows = [[int(i) for i in r] for r in rows]
            for r in rows:
                if len(r) > M:
                    raise ValueError(f"row carries {len(r)} segments, "
                                     f"cap is {M}")
                if int(all_len[r].sum()) > S:
                    raise ValueError("row overflows the packing width")
            n = sum(len(r) for r in rows)
        lengths = all_len  # assembly below indexes ORIGINAL example ids
        N = len(rows)
        src_ids = encoded.arrays["input_ids"]
        src_lab = encoded.arrays["label"]
        input_ids = np.zeros((N, S), np.int32)
        segment_ids = np.zeros((N, S), np.int32)
        position_ids = np.zeros((N, S), np.int32)
        cls_pos = np.zeros((N, M), np.int32)
        label = np.zeros((N, M), np.int32)
        weight = np.zeros((N, M), np.float32)
        source_rows: List[List[int]] = [list(r) for r in rows]
        for r, members in enumerate(rows):
            off = 0
            for s, orig in enumerate(members):
                L = int(lengths[orig])
                input_ids[r, off: off + L] = src_ids[orig, :L]
                segment_ids[r, off: off + L] = s + 1
                # positions restart per segment: each example sees exactly
                # the position embeddings its unpacked encoding would —
                # packed-vs-unpacked forward parity is exact, not modulo a
                # row-offset shift (tests/test_length.py pins it)
                position_ids[r, off: off + L] = np.arange(L, dtype=np.int32)
                cls_pos[r, s] = off
                label[r, s] = src_lab[orig]
                weight[r, s] = 1.0
                off += L
        self.arrays = {
            "input_ids": input_ids,
            "segment_ids": segment_ids,
            "position_ids": position_ids,
            "attention_mask": (segment_ids > 0).astype(np.int32),
            "token_type_ids": np.zeros((N, S), np.int32),
            "cls_positions": cls_pos,
            "label": label,
            "example_weight": weight,
        }
        self.n = N
        self.seq_len = S
        self.width = S
        self.max_segments = M
        self.num_examples = n
        #: per packed row, the ORIGINAL encoded-example indices riding it
        #: (coverage/parity tests and the multi-width container use it)
        self.source_rows = source_rows

    def stats(self) -> Dict[str, float]:
        """Packing efficiency numbers (fill, segments a row)."""
        seg_counts = (self.arrays["example_weight"] > 0).sum(1)
        tokens_real = int(self.arrays["attention_mask"].sum())
        return {
            "rows": self.n,
            "examples": self.num_examples,
            "tokens_real": tokens_real,
            "fill_ratio": tokens_real / float(self.n * self.seq_len)
            if self.n else 0.0,
            "segments_per_row_mean": float(seg_counts.mean())
            if self.n else 0.0,
            "segments_per_row_max": int(seg_counts.max()) if self.n else 0,
        }


def pack_classification(encoded: EncodedDataset, max_segments: int = 16
                        ) -> PackedClassificationDataset:
    """Pack an encoded classification split into multi-example rows."""
    return PackedClassificationDataset(encoded, max_segments=max_segments)


class MultiWidthPackedDataset:
    """The multi-width pack layout (``--length_mode pack`` with several
    kernel-tiling widths in ``--length_buckets``): each example lands in
    the SMALLEST covering width bucket and each bucket packs at its own
    width (one :class:`PackedClassificationDataset` per width, segment cap
    scaled by :func:`segment_cap`), so a long-document split does not pad
    its short tail up to the long width — short docs ride dense 128/256
    rows while the long ones pack 512/1024/2048 rows, all on the exact
    channel layout the segment-native flash kernel consumes.

    Packing runs WIDEST-FIRST with backfill: a width's rows are seeded by
    the examples that NEED it (covering width = this width) via
    best-fit-decreasing, then topped up from the still-unpacked shorter
    examples (longest first, same best-fit placement) — a 512-wide row
    holding one 300-token document backfills with ~200 tokens of short
    documents instead of padding.  Without backfill the per-row residue
    caps fill near the mean member length over the width (~0.75); with it
    the fill clears 0.85 on the long-document mix.

    Rows live in ONE global index space (width groups concatenated in
    ascending width order); batching rides the ordinary
    :class:`~pdnlp_tpu.data.sampler.LengthGroupedSampler` over
    :meth:`row_width_table` with the widths as the buckets — batches stay
    width-homogeneous, the compile count stays bounded at
    ``len(widths) x step-variants``, and the epoch structure is
    epoch-invariant, exactly the bucket-mode contract.  Not an
    :class:`~pdnlp_tpu.data.collate.EncodedDataset` (there is no single
    rectangular array), so the device-resident pipeline declines it and
    ``--pipeline auto`` falls back to prefetch (``tests/test_longcontext.py``).
    """

    def __init__(self, encoded: EncodedDataset, widths: Sequence[int],
                 max_segments: int = 16, base_width: int = 128):
        ws = tuple(sorted(int(w) for w in set(widths)))
        if not ws:
            raise ValueError("need at least one packing width")
        lengths = encoded.lengths()
        if len(encoded) and int(lengths.max()) > ws[-1]:
            raise ValueError(
                f"longest example ({int(lengths.max())} tokens) exceeds "
                f"the largest packing width {ws[-1]} — include a covering "
                "width in --length_buckets")
        edges = np.asarray(ws, np.int64)
        member = edges[np.minimum(np.searchsorted(edges, lengths),
                                  len(edges) - 1)]
        # widest-first with backfill (class docstring): each width packs
        # its REQUIRED members, then draws from the shorter remainder
        remaining = {w: set(np.flatnonzero(member == w).tolist())
                     for w in ws}
        rows_by_width: Dict[int, List[List[int]]] = {}
        for w in reversed(ws):
            packer = _BfdPacker(w, segment_cap(w, max_segments, base_width))
            need = sorted(remaining[w], key=lambda i: (-lengths[i], i))
            remaining[w] = set()
            for i in need:                # seed: the width's own members
                packer.place(i, int(lengths[i]))
            pool = sorted((i for w2 in ws if w2 < w for i in remaining[w2]),
                          key=lambda i: (-lengths[i], i))
            for i in pool:                # backfill: no new rows opened
                if not packer.has_open:
                    break
                if packer.place(i, int(lengths[i]), open_new=False):
                    remaining[edges[np.searchsorted(edges,
                                                    lengths[i])]].discard(i)
            if packer.rows:
                rows_by_width[w] = packer.rows
        self.widths = ws
        self.groups: Dict[int, PackedClassificationDataset] = {}
        self._offsets: Dict[int, int] = {}
        off = 0
        for w in ws:
            if w not in rows_by_width:
                continue
            g = PackedClassificationDataset(
                encoded, max_segments=segment_cap(w, max_segments,
                                                  base_width),
                width=w, rows=rows_by_width[w])
            self.groups[w] = g
            self._offsets[w] = off
            off += g.n
        self.n = off
        self.seq_len = ws[-1]          # widest row (HBM-budget shape)
        self.num_examples = len(encoded)

    def __len__(self) -> int:
        return self.n

    def row_width_table(self) -> np.ndarray:
        """[n] row widths — the ``lengths`` input of the
        ``LengthGroupedSampler`` that batches this dataset (with
        ``buckets=self.widths`` the covering bucket IS the row's width)."""
        out = np.zeros((self.n,), np.int64)
        for w, g in self.groups.items():
            off = self._offsets[w]
            out[off: off + g.n] = w
        return out

    def lengths(self) -> np.ndarray:
        """Real token count per packed row (parity with EncodedDataset)."""
        out = np.zeros((self.n,), np.int64)
        for w, g in self.groups.items():
            off = self._offsets[w]
            out[off: off + g.n] = g.lengths()
        return out

    def take(self, indices: Sequence[int], pad_to: int = 0,
             seq_len: int = 0) -> Dict[str, np.ndarray]:
        """Assemble one width-homogeneous batch of packed rows.

        ``seq_len`` names the batch's width (the sampler supplies it);
        every index must belong to that width's group — the sampler
        guarantees it, and mixing widths is a hard error, not a pad."""
        w = int(seq_len) or self.seq_len
        if w not in self.groups:
            raise ValueError(f"no packed rows at width {w} "
                             f"(have {sorted(self.groups)})")
        off, g = self._offsets[w], self.groups[w]
        local = np.asarray(indices, np.int64) - off
        if len(local) and (local.min() < 0 or local.max() >= g.n):
            raise ValueError(
                f"batch mixes widths: indices outside the width-{w} group")
        return g.take(local, pad_to=pad_to)

    def stats(self) -> Dict[str, object]:
        """Per-width packing stats + the token-weighted aggregate fill."""
        per = {int(w): g.stats() for w, g in self.groups.items()}
        slots = sum(g.n * w for w, g in self.groups.items())
        real = sum(int(g.arrays["attention_mask"].sum())
                   for g in self.groups.values())
        return {"by_width": per,
                "rows": self.n,
                "examples": self.num_examples,
                "fill_ratio": real / float(slots) if slots else 0.0}


def pack_id_lists(
    id_lists: Sequence[Sequence[int]],
    seq_len: int,
    rows: int,
    max_segments: int,
    pad_id: int = 0,
) -> Tuple[Dict[str, np.ndarray], List[Optional[Tuple[int, int]]]]:
    """Bin-pack ragged token-id lists into ONE fixed ``[rows, seq_len]``
    packed batch — the online-serving twin of
    :class:`PackedClassificationDataset` (same channel layout, so
    ``models.bert.classify`` and the pallas segment kernel consume it
    unchanged), minus the label/weight channels serving never has.

    The caller's order IS the priority order (the serve batcher sorts by
    remaining deadline slack, lowest first, so the most urgent requests
    close the earliest rows): placement is first-fit over the open rows in
    order, and a list that fits nowhere right now is *skipped* — it could
    not ride this batch anyway — while later, shorter lists may still fill
    the gaps it left.

    Returns ``(batch, placements)`` where ``placements[i]`` is the
    ``(row, slot)`` the ``i``-th list landed at, or ``None`` if it did not
    fit (the caller keeps it queued for the next batch).  ``batch`` always
    has the full ``rows`` x ``seq_len`` shape (unused rows stay padding)
    so the packed forward is one compiled program per ``(rows, seq_len)``
    — retrace-free by construction.
    """
    S, R, M = int(seq_len), int(rows), int(max_segments)
    if R < 1 or M < 1:
        raise ValueError(f"need rows >= 1 and max_segments >= 1, "
                         f"got rows={R} max_segments={M}")
    input_ids = np.full((R, S), pad_id, np.int32)
    segment_ids = np.zeros((R, S), np.int32)
    position_ids = np.zeros((R, S), np.int32)
    cls_pos = np.zeros((R, M), np.int32)
    used = [0] * R     # tokens occupied per row
    segs = [0] * R     # segments opened per row
    opened = 0         # rows touched so far (first-fit opens them in order)
    placements: List[Optional[Tuple[int, int]]] = []
    for ids in id_lists:
        L = len(ids)
        if L > S:
            raise ValueError(f"list of {L} tokens exceeds the {S}-token "
                             "pack width — truncate before packing")
        if L == 0:
            # an empty list would open a phantom segment whose
            # cls_positions entry aliases the NEXT segment's offset — its
            # caller would silently receive a neighbor's logits.  Callers
            # (serve submit paths) reject empties before packing.
            raise ValueError("empty id list cannot be packed — reject "
                             "empty requests before batch formation")
        row = next((r for r in range(opened)
                    if segs[r] < M and used[r] + L <= S), None)
        if row is None:
            if opened >= R:
                placements.append(None)  # full batch: ride the next one
                continue
            row = opened
            opened += 1
        off = used[row]
        input_ids[row, off: off + L] = np.asarray(ids, np.int32)
        segment_ids[row, off: off + L] = segs[row] + 1
        # positions restart per segment — exact embedding parity with the
        # request's own padded forward (the training packer's contract)
        position_ids[row, off: off + L] = np.arange(L, dtype=np.int32)
        cls_pos[row, segs[row]] = off
        placements.append((row, segs[row]))
        used[row] += L
        segs[row] += 1
    batch = {
        "input_ids": input_ids,
        "segment_ids": segment_ids,
        "position_ids": position_ids,
        "attention_mask": (segment_ids > 0).astype(np.int32),
        "token_type_ids": np.zeros((R, S), np.int32),
        "cls_positions": cls_pos,
    }
    return batch, placements


def segment_bias(segment_ids: np.ndarray, dtype=np.float32) -> np.ndarray:
    """`[B, S]` segment ids -> `[B, 1, S, S]` additive attention bias.

    0 where query and key share a (nonzero) segment, -1e9 elsewhere — the
    block-diagonal mask that keeps packed texts independent.  Pure
    arithmetic/broadcast ops so the same function traces under jit (jnp
    arrays) and runs on host numpy.

    This is the XLA FALLBACK materialization only: the routed default
    passes the raw ``segment_ids`` down (``models.bert`` ->
    ``ops.attention``) and the pallas flash kernel derives the mask
    in-VMEM from the IDs — the quadratic [B, 1, S, S] tensor never
    reaches HBM.  ``ops.attention.dot_product_attention`` calls this only
    when the XLA path executes; nothing upstream should.
    """
    q = segment_ids[:, :, None]
    k = segment_ids[:, None, :]
    same = ((q == k) & (q > 0)).astype(dtype)
    return ((1.0 - same) * -1e9)[:, None, :, :]
