"""Sharded experiment assembly + compiled parallel steps.

This is where strategy becomes *placement*: the same step functions from
``train.steps`` get compiled with explicit mesh shardings.

- ``setup_sharded_model``: init the train state **already sharded** — the
  shardings are computed from ``jax.eval_shape`` (no memory), then the init
  runs under ``jit`` with ``out_shardings``, so a ZeRO run never materializes
  a full replica (the analog of DeepSpeed partitioning params at init,
  ``/root/reference/multi-gpu-deepspeed-cls.py:296-302``).
- ``make_parallel_train_step`` / ``make_parallel_eval_step``: ``jit`` with
  in/out shardings — XLA inserts the gradient all-reduce (DDP's NCCL hooks)
  or all-gather/reduce-scatter (ZeRO-3) on ICI.
- ``make_shardmap_train_step``: the explicit-collectives flavor (Horovod
  analog, ``/root/reference/multi-gpu-horovod-cls.py:338-350``): per-device
  code with hand-written ``psum`` of bf16-compressed gradients.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pdnlp_tpu.models import BertConfig, bert, get_config
from pdnlp_tpu.models.config import args_overrides
from pdnlp_tpu.parallel import collectives
from pdnlp_tpu.parallel.mesh import DATA_AXIS
from pdnlp_tpu.parallel.sharding import batch_sharding, replicated, state_shardings
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.precision import resolve_dtype
from pdnlp_tpu.train.steps import (
    State, build_eval_step, build_train_step, init_state, weighted_ce,
)
from pdnlp_tpu.utils.seeding import set_seed


def setup_sharded_model(args, vocab_size: int, mesh: Mesh, mode: str = "dp",
                        total_steps: int = None
                        ) -> Tuple[BertConfig, optax.GradientTransformation, State, Any]:
    """(cfg, tx, state, shardings) — state lives on the mesh from birth.

    ``total_steps`` sizes the optional LR schedule (``--lr_schedule``);
    required when one is configured."""
    from pdnlp_tpu.train.optim import make_schedule
    from pdnlp_tpu.utils.seeding import train_key

    cfg = get_config(args.model, vocab_size=vocab_size, num_labels=args.num_labels,
                     dropout=args.dropout, attn_dropout=args.attn_dropout,
                     **args_overrides(args))
    if mode == "tp":
        from pdnlp_tpu.parallel.sharding import MODEL_AXIS

        if cfg.moe_experts:
            raise ValueError("tp does not support MoE models (the expert "
                             "dim needs the ep mode's placement)")
        m = mesh.shape.get(MODEL_AXIS, 1)
        if cfg.num_heads % m or cfg.intermediate_size % m:
            raise ValueError(
                f"tensor-parallel degree {m} must divide num_heads "
                f"({cfg.num_heads}) and intermediate_size "
                f"({cfg.intermediate_size}) — heads and MLP features split "
                "across the model axis")
    if mode == "ep":
        from pdnlp_tpu.parallel.sharding import EXPERT_AXIS

        e = mesh.shape.get(EXPERT_AXIS, 1)
        if not cfg.moe_experts:
            raise ValueError(f"ep needs an MoE model ({args.model} is "
                             "dense) — use bert-base-moe / bert-tiny-moe "
                             "or set moe_experts")
        if cfg.moe_experts % e:
            raise ValueError(f"expert-parallel degree {e} must divide "
                             f"moe_experts ({cfg.moe_experts})")
    root = set_seed(args.seed)
    init_key, _ = jax.random.split(root)
    train_rng = train_key(args.seed, getattr(args, "rng_impl", "rbg"))

    # tx needs a params *structure* for the weight-decay mask — shapes only.
    param_shapes = jax.eval_shape(lambda k: bert.init_params(k, cfg), init_key)
    tx = build_optimizer(param_shapes, args,
                         schedule=make_schedule(args, total_steps))

    def init_fn(key, rng):
        params = bert.init_params(key, cfg)
        return init_state(key, cfg, tx, rng=rng, params=params,
                          ema=getattr(args, "ema_decay", 0.0) > 0)

    state_shapes = jax.eval_shape(init_fn, init_key, train_rng)
    shardings = state_shardings(state_shapes, mesh, mode)
    offload = getattr(args, "offload_opt_state", False)
    state = jax.jit(init_fn, out_shardings=shardings)(init_key, train_rng)
    if offload:
        # Adam moments move to host RAM (DeepSpeed offload_optimizer
        # analog); the train step stages them explicitly.  The move happens
        # EAGERLY after init — memory-kind annotations inside the init jit
        # would spread to its integer outputs, which XLA's SPMD partitioner
        # rejects ("Side-effect HLO must have sharding" on s32 scalars).
        from pdnlp_tpu.parallel.sharding import with_memory_kind

        shardings = dict(shardings)
        shardings["opt_state"] = with_memory_kind(
            shardings["opt_state"], "pinned_host",
            shape_tree=state_shapes["opt_state"])
        state["opt_state"] = jax.device_put(state["opt_state"],
                                            shardings["opt_state"])
    if getattr(args, "init_from", None):
        # warm-start the encoder from an in-repo pretrain checkpoint (the
        # from_pretrained analog); head stays fresh, placement is preserved
        # (ZeRO leaves go straight to their shards)
        from pdnlp_tpu.train.pretrain import load_encoder

        params = load_encoder(args.init_from, state["params"],
                              head=getattr(args, "init_head", False))
        state["params"] = jax.device_put(params, shardings["params"])
        if "ema" in state:  # the EMA tracks the WARM-STARTED weights
            state["ema"] = jax.device_put(params, shardings["ema"])
    if "ema" in state:
        # force DISTINCT buffers: the init jit (and device_put's cache) may
        # alias the identical params/ema values to one buffer — the first
        # donated train step would then invalidate both references
        # (observed as "TPU backend error (InvalidArgument)" at eval fetch)
        state["ema"] = jax.tree_util.tree_map(jnp.copy, state["ema"])
    return cfg, tx, state, shardings


def make_parallel_train_step(cfg: BertConfig, tx, args, mesh: Mesh, shardings):
    """Compile the fused train step over the mesh.  DP vs ZeRO is entirely
    encoded in ``shardings`` — the step function is identical."""
    opt_staging = None
    if getattr(args, "offload_opt_state", False):
        from jax.sharding import NamedSharding

        # host-kind leaves (the float moments) stage to device and back;
        # everything else keeps its original sharding — explicit memory-kind
        # annotations on replicated integer scalars break SPMD partitioning
        def to_device(s):
            if getattr(s, "memory_kind", None) == "pinned_host":
                return NamedSharding(s.mesh, s.spec, memory_kind="device")
            return s

        opt_staging = (jax.tree_util.tree_map(to_device, shardings["opt_state"]),
                       shardings["opt_state"])
    fn = build_train_step(cfg, tx, args, opt_staging=opt_staging)
    return jax.jit(
        fn,
        donate_argnums=0,
        in_shardings=(shardings, batch_sharding(mesh)),
        out_shardings=(shardings, replicated(mesh)),
    )


def make_parallel_multi_step(cfg: BertConfig, tx, args, mesh: Mesh, shardings):
    """K-step fused variant of ``make_parallel_train_step`` (batches carry a
    leading unsharded ``[K]`` axis; batch dim shards over ``data``)."""
    from jax.sharding import NamedSharding
    from pdnlp_tpu.train.steps import build_multi_step

    fn = build_multi_step(build_train_step(cfg, tx, args))
    batch_sh = NamedSharding(mesh, P(None, DATA_AXIS))
    metrics_sh = replicated(mesh)
    return jax.jit(
        fn,
        donate_argnums=0,
        in_shardings=(shardings, batch_sh),
        out_shardings=(shardings, metrics_sh),
    )


def make_parallel_eval_step(cfg: BertConfig, args, mesh: Mesh, param_shardings):
    """Eval step over the mesh; outputs replicated so every host can read
    them (the ``output_reduce`` all-gather, ``multi-gpu-distributed-cls.py:
    145-155``, inserted by XLA)."""
    fn = build_eval_step(cfg, args)
    return jax.jit(
        fn,
        in_shardings=(param_shardings, batch_sharding(mesh)),
        out_shardings=replicated(mesh),
    )


def make_shardmap_train_step(cfg: BertConfig, tx, args, mesh: Mesh,
                             compress_grads: bool = True):
    """Explicit-collectives train step (Horovod analog).

    Per-device body: local forward/backward on the batch shard, then a
    hand-written weighted ``psum`` of gradients — optionally compressed to
    bf16 on the wire (``hvd.Compression.fp16``,
    ``/root/reference/multi-gpu-horovod-cls.py:344-349``) — then an identical
    replicated optimizer update on every device.

    Exactness: the global loss is sum(w*ce)/sum(w) over the *global* batch.
    Each shard computes its local weighted-mean grad; shards are then
    combined weighted by their local weight mass, which reproduces the
    global-mean gradient exactly even when filler rows make shards uneven.
    """
    from pdnlp_tpu.train.steps import _unroll

    if getattr(args, "ema_decay", 0.0) > 0:
        raise ValueError("--ema_decay runs on the jit strategies (dp/zero/"
                         "tp/ep) — the shard_map step does not maintain the "
                         "EMA tree and would silently evaluate stale "
                         "weights")
    dtype = resolve_dtype(args.dtype)
    remat = bool(args.remat)
    attn_impl = args.attention_impl  # ops.attention routes "auto" per trace
    compress = jnp.bfloat16 if compress_grads else None
    unroll = _unroll(args)
    smoothing = args.label_smoothing

    def local_loss(params, batch, rng):
        # MoE aux (0 for dense): computed over the LOCAL shard's batch and
        # weight-averaged across shards with the loss below — a per-shard
        # estimator of the balancing statistics, vs the jit paths' global-
        # batch one (the standard per-device formulation; both pressure the
        # router identically in expectation).  It joins the optimized
        # objective only — the reported loss stays bare CE.
        logits, aux = bert.classify(params, cfg, batch, dtype=dtype,
                                    deterministic=False, rng=rng, remat=remat,
                                    attn_impl=attn_impl, unroll=unroll,
                                    return_aux=True)
        loss, correct, objective = weighted_ce(
            logits, batch["label"], batch["example_weight"],
            smoothing=smoothing)
        return objective + cfg.moe_aux_coef * aux, (
            loss, correct, batch["example_weight"].sum())

    def per_device(state: State, batch) -> Tuple[State, Dict[str, jax.Array]]:
        # distinct dropout stream per shard, common stream per step
        rng = jax.random.fold_in(state["rng"], state["step"])
        rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
        (_, (loss, correct, lw)), grads = jax.value_and_grad(
            local_loss, has_aux=True)(state["params"], batch, rng)
        from pdnlp_tpu.parallel.collectives import weighted_shard_scale

        scale, gw = weighted_shard_scale(lw, DATA_AXIS)
        grads = jax.tree_util.tree_map(
            lambda g: (jax.lax.psum((g * scale).astype(compress), DATA_AXIS)
                       .astype(g.dtype)) if compress is not None
            else jax.lax.psum(g * scale, DATA_AXIS),
            grads,
        )
        loss = jax.lax.psum(loss * scale, DATA_AXIS)
        acc = jax.lax.psum(correct, DATA_AXIS) / gw
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1, "rng": state["rng"]}
        return new_state, {"loss": loss, "accuracy": acc}

    batch_specs = P(DATA_AXIS)
    mapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), batch_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=0)
