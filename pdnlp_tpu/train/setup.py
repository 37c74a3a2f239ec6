"""Shared experiment assembly — what the reference copy-pastes 9×, built once.

Every reference script repeats the same ~60 lines: seed, load/split data,
tokenizer, loaders, model, optimizer (e.g. ``/root/reference/single-gpu-cls.py:
207-255``).  Entry scripts here call these two functions and stay thin; the
*strategy* (placement/sharding/launcher) is the only thing they add.
"""
from __future__ import annotations

from typing import Tuple

import jax

from pdnlp_tpu.data import Collator, DataLoader, WordPieceTokenizer, load_data, split_data
from pdnlp_tpu.data.sampler import DistributedShardSampler
from pdnlp_tpu.data.tokenizer import get_or_build_vocab
from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.models.config import args_overrides
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.utils.seeding import set_seed


def setup_data(args, *, num_shards: int = 1, shard_id: int = 0,
               device_batch_mult: int = 1,
               train_override=None) -> Tuple[DataLoader, DataLoader, WordPieceTokenizer]:
    """(train_loader, dev_loader, tokenizer).

    ``device_batch_mult`` scales the per-host batch for single-controller
    data parallelism (global batch = per-device 32 × #devices, so step count
    matches the reference's ``DistributedSampler`` math: 288 single / 144 at
    2-way).  ``num_shards``/``shard_id`` split the *dataset* across host
    processes for the multi-process launcher variants.  ``train_override``
    replaces the train split's examples (the supervised-pretrain stage trains
    on the labeled externals while keeping the standard dev split).
    """
    data = load_data(args.data_path)
    train, dev = split_data(data, seed=args.seed, limit=args.data_limit, ratio=args.ratio)
    if train_override is not None:
        train = list(train_override)
    tok = WordPieceTokenizer(get_or_build_vocab(args))
    from pdnlp_tpu.data import native

    native.attach(tok)  # no-op unless `make -C csrc` has been run
    col = Collator(tok, args.max_seq_len)
    from pdnlp_tpu.data.collate import EncodedDataset

    # one-time encode of each split: epochs re-index cached arrays instead
    # of re-tokenizing (identical bytes either way — Collator stays the
    # reference-semantics spec and the parity test pins them equal)
    train_enc = EncodedDataset(train, tok, args.max_seq_len)
    dev_enc = EncodedDataset(dev, tok, args.max_seq_len)
    train_loader = build_length_train_loader(
        args, train, col, train_enc,
        batch_size=args.train_batch_size * device_batch_mult,
        num_shards=num_shards, shard_id=shard_id)
    dev_loader = DataLoader(
        dev, col, args.dev_batch_size * device_batch_mult,
        sampler=DistributedShardSampler(len(dev), num_shards, shard_id, shuffle=False),
        prefetch=args.prefetch, encoded=dev_enc,
    )
    return train_loader, dev_loader, tok


def build_length_train_loader(args, train, col, train_enc, *, batch_size,
                              num_shards: int = 1, shard_id: int = 0):
    """The train ``DataLoader`` under ``--length_mode`` — ONE place, shared
    by ``setup_data`` and ``tests/test_length.py``, so the mode wiring cannot
    drift between the entrypoints and the tests that hold them.

    - ``full``: the reference path — seeded shard sampler, every batch
      padded to ``max_seq_len``.
    - ``bucket``: seeded length-grouped sampler; each batch pads to the
      smallest bucket covering its longest example, batches stay
      bucket-homogeneous (and ``fuse_steps`` groups shape-homogeneous).
    - ``pack``: the split is packed once into multi-example rows
      (``data.packing``); epochs shuffle packed rows through the ordinary
      shard sampler — one static shape, ~1/segments-per-row the steps.
      When ``--length_buckets`` names SEVERAL kernel-tiling widths
      (multiples of 128) whose largest covers the encode width, packing
      goes multi-width (``MultiWidthPackedDataset``): each example packs
      at its smallest covering width, per-width segment caps
      (``data.packing.segment_cap``), and the length-grouped sampler
      batches width-homogeneous packed rows — the long-document layout
      the segment-native flash kernel serves at 512-2048.

    Both bucket and pack validate the bucket widths against the model's
    position-table size at setup (``validate_length_buckets``) — an
    out-of-table width would silently gather garbage embeddings (JAX
    clamps the gather), so it is a loud setup error instead.

    Eval loaders stay unpacked/full-width in every mode: eval semantics
    (and the dev-accuracy definition) never change with the training
    layout.
    """
    from pdnlp_tpu.data.packing import (
        MultiWidthPackedDataset, pack_classification,
    )
    from pdnlp_tpu.data.sampler import (
        LengthGroupedSampler, parse_buckets, resolve_length_mode,
        validate_length_buckets,
    )
    from pdnlp_tpu.models import get_config

    mode = resolve_length_mode(args)
    if mode in ("bucket", "pack"):
        widths = parse_buckets(args.length_buckets, args.max_seq_len)
        validate_length_buckets(
            widths, max_position=get_config(args.model).max_position,
            model=args.model, mode=mode, max_seq_len=args.max_seq_len)
    if mode == "bucket":
        sampler = LengthGroupedSampler(
            train_enc.lengths(), batch_size=batch_size,
            buckets=widths,
            num_shards=num_shards, shard_id=shard_id, shuffle=True,
            seed=args.seed)
        return DataLoader(train, col, batch_size, sampler=sampler,
                          prefetch=args.prefetch, encoded=train_enc)
    if mode == "pack":
        cap = getattr(args, "pack_max_segments", 16)
        # multi-width needs >1 kernel-tiling width AND coverage of the
        # encode width; otherwise the legacy single-width pack (one
        # static shape at max_seq_len, resident-pipeline-eligible) stands
        tiling = tuple(w for w in widths if w >= 128 and w % 128 == 0)
        if len(tiling) > 1 and tiling[-1] >= args.max_seq_len:
            packed = MultiWidthPackedDataset(train_enc, tiling,
                                             max_segments=cap)
            sampler = LengthGroupedSampler(
                packed.row_width_table(), batch_size=batch_size,
                buckets=tiling, num_shards=num_shards, shard_id=shard_id,
                shuffle=True, seed=args.seed)
            return DataLoader(train, col, batch_size, sampler=sampler,
                              prefetch=args.prefetch, encoded=packed)
        packed = pack_classification(train_enc, max_segments=cap)
        return DataLoader(
            train, col, batch_size,
            sampler=DistributedShardSampler(len(packed), num_shards,
                                            shard_id, shuffle=True,
                                            seed=args.seed),
            prefetch=args.prefetch, encoded=packed)
    return DataLoader(
        train, col, batch_size,
        sampler=DistributedShardSampler(len(train), num_shards, shard_id,
                                        shuffle=True, seed=args.seed),
        prefetch=args.prefetch, encoded=train_enc)


def setup_pipeline(args, loader, put=None, put_fused=None, mesh=None,
                   allow_resident: bool = True):
    """The input pipeline for a wired loader (``data.pipeline``): resident
    (split held in HBM, zero steady-state transport) / double-buffered
    prefetch / sync behind ``--pipeline``; shared by the strategy runners
    and the single-device entrypoint so the mode decision can't drift.

    Configures the obs tracer from ``--trace`` FIRST: the resident
    pipeline's one-time residency upload happens inside ``build_pipeline``
    and must land in the trace, not precede it."""
    from pdnlp_tpu.data.pipeline import build_pipeline
    from pdnlp_tpu.obs.trace import configure_from_args

    configure_from_args(args)
    return build_pipeline(args, loader, put=put, put_fused=put_fused,
                          mesh=mesh, allow_resident=allow_resident)


def setup_model(args, vocab_size: int, total_steps: int = None):
    """(cfg, tx, state) — seeded the reference's way (one seed, 123).
    ``total_steps`` sizes the optional ``--lr_schedule``."""
    from pdnlp_tpu.train.optim import make_schedule
    from pdnlp_tpu.train.steps import init_state
    from pdnlp_tpu.utils.seeding import train_key

    if getattr(args, "offload_opt_state", False):
        raise ValueError("--offload_opt_state is wired into the mesh "
                         "strategies (dp/zero via build_parallel_trainer), "
                         "not this entrypoint — it would be silently ignored "
                         "here")
    cfg = get_config(args.model, vocab_size=vocab_size, num_labels=args.num_labels,
                     dropout=args.dropout, attn_dropout=args.attn_dropout,
                     **args_overrides(args))
    root = set_seed(args.seed)
    init_key, _ = jax.random.split(root)
    train_rng = train_key(args.seed, getattr(args, "rng_impl", "rbg"))
    params = bert.init_params(init_key, cfg)
    if getattr(args, "init_from", None):
        from pdnlp_tpu.train.pretrain import load_encoder

        params = load_encoder(args.init_from, params,
                              head=getattr(args, "init_head", False))
    tx = build_optimizer(params, args,
                         schedule=make_schedule(args, total_steps))
    state = init_state(init_key, cfg, tx, rng=train_rng, params=params,
                       ema=getattr(args, "ema_decay", 0.0) > 0)
    return cfg, tx, state
