"""One shared parallel runner behind every multi-device entrypoint.

The reference's nine scripts each re-assemble the same experiment around a
different wrapper (DDP / Horovod / DeepSpeed / ...).  Here the experiment is
assembled once and the *strategy* is three knobs:

- ``mode``: ``"dp"`` (replicated state — DDP analog) or ``"zero"`` (fully
  sharded state — DeepSpeed ZeRO-3 analog);
- ``explicit_collectives``: compile through ``shard_map`` with hand-written
  ``psum`` + bf16 gradient compression (Horovod analog) instead of letting
  XLA insert collectives from shardings;
- ``scale_batch``: ``True`` scales the global batch by the data-axis size so
  steps shrink with devices (DDP's ``DistributedSampler`` math: 144 @ 2-way);
  ``False`` keeps the reference's ``nn.DataParallel`` semantics — same
  32-row global batch scattered over devices, step count unchanged (288)
  (``/root/reference/multi-gpu-dataparallel-cls.py:255``, ``README.md:44-74``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from pdnlp_tpu.data.corpus import LABELS
from pdnlp_tpu.parallel import (
    local_batch_mult, make_global_batch, make_mesh, make_parallel_eval_step,
    make_parallel_train_step, make_shardmap_train_step, init_runtime,
    setup_sharded_model,
)
from pdnlp_tpu.parallel.execution import make_parallel_multi_step
from pdnlp_tpu.train.setup import setup_data, setup_pipeline
from pdnlp_tpu.train.trainer import Trainer
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.logging import rank0_print
from pdnlp_tpu.utils.metrics import classification_report


def build_parallel_trainer(
    args: Args,
    *,
    mode: str = "dp",
    explicit_collectives: bool = False,
    scale_batch: bool = True,
    mesh=None,
    train_override=None,
) -> Tuple[Trainer, object, object]:
    """(trainer, train_loader, dev_loader) wired for the given strategy.

    ``train_override`` swaps the train split's examples (supervised-pretrain
    stage); everything else — dev split, mesh, sharding, step — is shared."""
    if mesh is None:
        proc0 = init_runtime(args)[0] == 0  # noqa: F841  (rendezvous side effect)
        mesh = make_mesh(num_devices=args.num_devices, shape=args.mesh_shape)
    if getattr(args, "offload_opt_state", False) and (
            explicit_collectives or args.fuse_steps > 1 or mode == "tp"):
        raise ValueError("--offload_opt_state works with the jit dp/zero "
                         "strategies, not shard_map, fused multi-steps, or "
                         "tp — the staged host<->device transfers are only "
                         "wired into the plain data-axis train step")
    if not explicit_collectives:
        # the jit strategies let GSPMD partition the step, which Mosaic
        # kernels cannot follow; pinned HERE so the steps, the Trainer's
        # surfaced impl and a run's report all read the same args
        from pdnlp_tpu.ops.attention import pin_auto_for_mesh

        args = args.replace(
            attention_impl=pin_auto_for_mesh(args.attention_impl, mesh),
            fused_ce=pin_auto_for_mesh(args.fused_ce, mesh, "fused_ce"))
    from pdnlp_tpu.data.sampler import resolve_length_mode

    if explicit_collectives and resolve_length_mode(args) != "full":
        raise ValueError(
            "--length_mode bucket/pack is wired into the jit strategies "
            "(shapes re-specialize per bucket; packed batches carry extra "
            "channels) — the hand-written shard_map step compiles one "
            "fixed-shape program; use the dp/zero jit path instead")
    if scale_batch:
        # which slice of the global batch this process feeds — handles both
        # a data axis split across processes (dp/zero: each host its shard)
        # and one replicated across them (e.g. tp/ep with the model/expert
        # axis spanning the process boundary: every host the full batch)
        from pdnlp_tpu.parallel.mesh import local_data_extent

        num_shards, shard_id, mult = local_data_extent(mesh)
    else:
        num_shards, shard_id, mult = (jax.process_count(),
                                      jax.process_index(), 1)
    train_loader, dev_loader, tok = setup_data(
        args,
        num_shards=num_shards,
        shard_id=shard_id,
        device_batch_mult=mult,
        train_override=train_override,
    )
    cfg, tx, state, shardings = setup_sharded_model(
        args, tok.vocab_size, mesh, mode,
        total_steps=len(train_loader) * args.epochs)
    if explicit_collectives:
        train_step = make_shardmap_train_step(cfg, tx, args, mesh)
    else:
        train_step = make_parallel_train_step(cfg, tx, args, mesh, shardings)
    eval_step = make_parallel_eval_step(cfg, args, mesh, shardings["params"])
    multi_step = put_fused = None
    if args.fuse_steps > 1 and not explicit_collectives:
        multi_step = make_parallel_multi_step(cfg, tx, args, mesh, shardings)
        put_fused = make_global_batch(mesh, leading_stack=True)
    put = make_global_batch(mesh)
    pipeline = setup_pipeline(args, train_loader, put=put,
                              put_fused=put_fused, mesh=mesh)
    trainer = Trainer(args, cfg, state, train_step, eval_step,
                      put=put, multi_step=multi_step, put_fused=put_fused,
                      pipeline=pipeline)
    rank0_print(
        f"mesh: {dict(mesh.shape)}  process {jax.process_index()}/{jax.process_count()}"
        f"  mode: {mode}{' +shard_map' if explicit_collectives else ''}"
        f"  dtype: {args.dtype}  global batch: "
        f"{args.train_batch_size * mesh.shape.get('data', 1) if scale_batch else args.train_batch_size}"
        f"  steps/epoch: {len(train_loader)}  pipeline: {pipeline.mode}")
    return trainer, train_loader, dev_loader


def _try_resume(trainer, args: Args) -> None:
    """Restore the newest resume snapshot when one exists.  Same-width
    restores continue bitwise; a snapshot saved at a different data-
    parallel width reshards onto this mesh and remaps the data position
    (``Trainer.load_resume``/``_remap_elastic_width``).  A snapshot whose
    file AND retained previous are both corrupt degrades to a fresh start
    with a loud warning — for an elastic gang, re-training beats
    crash-looping the supervisor's restart budget away."""
    import os

    from pdnlp_tpu.train import checkpoint as ckpt

    if not (args.resume_from and os.path.exists(args.resume_path())):
        return
    try:
        trainer.load_resume(args.resume_path())
    except ckpt.CorruptCheckpointError as e:
        rank0_print(f"WARNING: resume snapshot unusable ({e}) — no valid "
                    "previous snapshot retained either; starting from "
                    "scratch")
        return
    rank0_print(f"resumed from {args.resume_path()} at step "
                f"{int(jax.device_get(trainer.state['step']))}")


def run_parallel(args: Args, **strategy) -> float:
    """Train + test; returns wall-clock minutes (the north-star metric)."""
    trainer, train_loader, dev_loader = build_parallel_trainer(args, **strategy)
    _try_resume(trainer, args)
    minutes = trainer.train(train_loader, dev_loader)
    result = trainer.test(dev_loader)
    rank0_print(f"test loss：{result['loss']:.6f} accuracy：{result['accuracy']:.4f}")
    rank0_print(classification_report(result["y_true"], result["y_pred"], LABELS))
    return minutes


def build_sp_trainer(args: Args, mesh=None):
    """(trainer, train_loader, dev_loader) for the sequence-parallel (ring
    attention) path — multi-process aware: on a mesh whose ``seq`` axis
    spans processes, the data axis is process-local, every process feeds the
    full global batch, and ``make_sp_batch`` hands each device its sequence
    slice (the ring's ``ppermute`` then crosses the process boundary)."""
    from pdnlp_tpu.data.sampler import resolve_length_mode
    from pdnlp_tpu.parallel import init_runtime, make_mesh
    from pdnlp_tpu.parallel.mesh import local_data_extent
    from pdnlp_tpu.parallel.sp import (
        SEQ, make_sp_batch, make_sp_eval_step, make_sp_train_step,
    )
    from pdnlp_tpu.train.setup import setup_model

    if resolve_length_mode(args) != "full":
        raise ValueError(
            "--length_mode bucket/pack is not wired into the sequence-"
            "parallel TRAINER yet: the ring/step layer itself speaks the "
            "packed channel layout as of PR 12 (per-hop shard-local masks, "
            "cross-shard [CLS] gather — parity in tests/test_longcontext."
            "py), but this entrypoint's loader/fuse wiring still assumes "
            "one full-width shape per step — use the dp/zero strategies "
            "for length-aware training")
    if mesh is None:
        init_runtime(args)
        shape = args.mesh_shape or {"data": 1, SEQ: len(jax.devices())}
        mesh = make_mesh(num_devices=args.num_devices, shape=shape)
    num_shards, shard_id, mult = local_data_extent(mesh)
    if jax.process_count() > 1 and num_shards > 1 \
            and local_data_extent(mesh, SEQ)[0] > 1:
        raise ValueError(
            "a mesh whose data AND seq axes both span processes needs "
            "per-process partial batches with seq slicing — order the mesh "
            "so one of the two axes stays process-local")
    train_loader, dev_loader, tok = setup_data(
        args, num_shards=num_shards, shard_id=shard_id,
        device_batch_mult=mult)
    cfg, tx, state = setup_model(args, tok.vocab_size,
                                 total_steps=len(train_loader) * args.epochs)
    example = next(iter(train_loader))
    train_step = make_sp_train_step(cfg, tx, args, mesh)(example)
    eval_step = make_sp_eval_step(cfg, args, mesh)(example)
    sp_put = make_sp_batch(mesh)
    # resident disallowed: the ring slices each batch along seq, not the
    # plain data-axis placement the resident gather produces
    pipeline = setup_pipeline(args, train_loader, put=sp_put,
                              allow_resident=False)
    trainer = Trainer(args, cfg, state, train_step, eval_step,
                      put=sp_put, pipeline=pipeline)
    rank0_print(f"mesh: {dict(mesh.shape)}  process "
                f"{jax.process_index()}/{jax.process_count()}  ring axis: "
                f"{SEQ} (local seq {args.max_seq_len // mesh.shape[SEQ]})  "
                f"steps/epoch: {len(train_loader)}")
    return trainer, train_loader, dev_loader


def run_sp(args: Args) -> float:
    """Train + test on the sequence-parallel path; returns wall-clock min."""
    trainer, train_loader, dev_loader = build_sp_trainer(args)
    minutes = trainer.train(train_loader, dev_loader)
    result = trainer.test(dev_loader)
    rank0_print(f"test loss：{result['loss']:.6f} accuracy：{result['accuracy']:.4f}")
    rank0_print(classification_report(result["y_true"], result["y_pred"], LABELS))
    return minutes


def build_pipeline_trainer(args: Args, mesh=None):
    """(trainer, train_loader, dev_loader) for the pipeline (GPipe) path —
    the ``pp`` twin of ``build_parallel_trainer``, multi-process aware: on a
    mesh whose ``stage`` (and optionally ``data``) axes span processes, each
    process feeds its data shard (or the full batch when there is no data
    axis — the batch is then replicated, stages exchange activations)."""
    from pdnlp_tpu.data.sampler import resolve_length_mode
    from pdnlp_tpu.parallel.pp import (
        STAGE, make_pp_batch, make_pp_eval_step, make_pp_train_step,
        setup_pp_model,
    )
    from pdnlp_tpu.parallel import init_runtime, make_mesh
    from pdnlp_tpu.parallel.mesh import local_data_extent

    if resolve_length_mode(args) != "full":
        raise ValueError(
            "--length_mode bucket/pack is not supported on the pipeline "
            "(GPipe) path: stages compile one fixed microbatch shape and "
            "the per-segment head gather lives on the last stage only — "
            "use the dp/zero strategies")
    if mesh is None:
        init_runtime(args)
        shape = args.mesh_shape or {STAGE: len(jax.devices())}
        mesh = make_mesh(num_devices=args.num_devices, shape=shape)
    # which slice of the global batch this process feeds: on a stage-major
    # multi-process mesh the data axis is replicated across processes and
    # every host feeds the full batch; on a data-major one each host feeds
    # its shard (local_data_extent covers both)
    num_shards, shard_id, mult = local_data_extent(mesh)
    train_loader, dev_loader, tok = setup_data(
        args, num_shards=num_shards, shard_id=shard_id,
        device_batch_mult=mult,
    )
    cfg, tx, state, _ = setup_pp_model(
        args, tok.vocab_size, mesh,
        total_steps=len(train_loader) * args.epochs)
    train_step = make_pp_train_step(cfg, tx, args, mesh,
                                    n_micro=args.microbatches)
    eval_step = make_pp_eval_step(cfg, args, mesh, n_micro=args.microbatches)
    pp_put = make_pp_batch(mesh)
    # resident disallowed: pp places batches along the stage-major layout,
    # not the plain data-axis sharding the resident gather produces
    pipeline = setup_pipeline(args, train_loader, put=pp_put,
                              allow_resident=False)
    trainer = Trainer(args, cfg, state, train_step, eval_step,
                      put=pp_put, pipeline=pipeline)
    rank0_print(f"mesh: {dict(mesh.shape)}  process "
                f"{jax.process_index()}/{jax.process_count()}  stages: "
                f"{mesh.shape[STAGE]} x {cfg.num_layers // mesh.shape[STAGE]}"
                f" layers  microbatches: {args.microbatches}  "
                f"steps/epoch: {len(train_loader)}")
    return trainer, train_loader, dev_loader


def run_pipeline(args: Args) -> float:
    """Train + test on the pipeline path; returns wall-clock minutes."""
    trainer, train_loader, dev_loader = build_pipeline_trainer(args)
    _try_resume(trainer, args)
    minutes = trainer.train(train_loader, dev_loader)
    result = trainer.test(dev_loader)
    rank0_print(f"test loss：{result['loss']:.6f} accuracy：{result['accuracy']:.4f}")
    rank0_print(classification_report(result["y_true"], result["y_pred"], LABELS))
    return minutes
