"""Train/eval step functions — the jitted hot loop.

The reference's hot loop is ``forward -> barrier -> backward(allreduce) ->
optimizer.step -> loss allreduce`` (``/root/reference/multi-gpu-distributed-
cls.py:165-181``).  Here the whole sequence is ONE XLA program: forward,
weighted-CE loss, backward, AdamW update, fused and compiled.  Parallelism is
chosen by *placement*, not by code: the same jitted step runs

- single-device when arrays live on one chip;
- data-parallel when the batch is sharded along the mesh ``data`` axis
  (XLA inserts the gradient all-reduce the reference does via NCCL);
- ZeRO/FSDP when params/opt-state are themselves sharded (XLA inserts
  all-gather/reduce-scatter, the ``zero_optimization`` analog of
  ``/root/reference/multi-gpu-deepspeed-cls.py:232-239``).

Loss semantics: per-example cross-entropy weighted by ``example_weight`` so
the static-shape filler rows of the last batch contribute nothing (the
reference instead runs a ragged 16-example 288th step).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from pdnlp_tpu.models import BertConfig, bert
from pdnlp_tpu.ops.fused_ce import fused_weighted_ce, resolve_fused_ce
from pdnlp_tpu.train.precision import resolve_dtype

State = Dict[str, Any]  # {'params', 'opt_state', 'step', 'rng'}
Metrics = Dict[str, jax.Array]


def _unroll(args):
    """Layer-scan unroll from ``Args``: None = full unroll (fastest
    measured), an int = that factor (1 = rolled scan, flat compile)."""
    u = getattr(args, "scan_unroll", None)
    return True if u is None else u


def init_state(key: jax.Array, cfg: BertConfig, tx: optax.GradientTransformation,
               rng: jax.Array = None, params=None, ema: bool = False) -> State:
    """Canonical train-state schema.  ``params`` may be passed pre-built
    (e.g. already sharded) to avoid re-initializing the full tree.
    ``ema=True`` adds an ``'ema'`` tree (initialized to the params) that the
    train step maintains as an exponential moving average — the weights
    eval/checkpointing then prefer (``--ema_decay``)."""
    if params is None:
        params = bert.init_params(key, cfg)
    state = {
        "params": params,
        "opt_state": tx.init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": rng if rng is not None else jax.random.key(0),
    }
    if ema:
        # jnp.copy, not asarray: distinct buffers, so a donated train step
        # can never invalidate params and ema together.  (Inside a jit init
        # XLA may still alias identical outputs — setup_sharded_model does
        # a post-jit copy for that path.)
        state["ema"] = jax.tree_util.tree_map(jnp.copy, params)
    return state


def cast_kernels(params, dtype):
    """Cast every ``kernel`` leaf with >=2 dims to ``dtype``, leaving
    embeddings, LayerNorm scales, and biases in fp32.

    The rule matches exactly the leaves ``bert._dense`` casts per-use, so a
    forward through the cast tree is bitwise identical to one through the
    fp32 masters — only gradient *materialization* changes dtype."""

    def cast(path, leaf):
        last = path[-1]
        if (getattr(last, "key", None) == "kernel"
                and getattr(leaf, "ndim", 0) >= 2):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(cast, params)


def weighted_ce(logits: jax.Array, labels: jax.Array, weights: jax.Array,
                smoothing: float = 0.0
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(weighted mean CE, weighted correct count, training objective);
    filler rows weigh 0.

    The first element is always the BARE cross-entropy — the reported
    metric, so smoothed and unsmoothed runs (and train vs eval lines) read
    on the same scale, mirroring how the MoE aux loss is kept out of the
    reported loss.  ``smoothing`` > 0 mixes the one-hot target with uniform
    mass eps/K (label smoothing) in the third element only; at 0 the
    objective is the bare CE array itself."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ce = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    wsum = jnp.maximum(weights.sum(), 1.0)
    loss = (ce * weights).sum() / wsum
    objective = loss
    if smoothing:
        uniform = ((-logp.mean(-1)) * weights).sum() / wsum
        objective = (1.0 - smoothing) * loss + smoothing * uniform
    correct = ((jnp.argmax(logits, -1) == labels) * weights).sum()
    return loss, correct, objective


def build_train_step(cfg: BertConfig, tx: optax.GradientTransformation, args,
                     opt_staging=None,
                     ) -> Callable[[State, Dict[str, jax.Array]], Tuple[State, Metrics]]:
    """The *unjitted* fused train step — callers choose how to compile it
    (plain ``jit``, ``jit`` with mesh shardings, or inside ``shard_map``).

    ``opt_staging``: ``(device_shardings, host_shardings)`` trees for the
    optimizer state when it lives in host memory (``--offload_opt_state``,
    the DeepSpeed ``offload_optimizer`` analog): the step explicitly stages
    moments host->device before the update and back after — XLA refuses
    mixed-memory-space arithmetic, so the transfers are part of the program.
    Measured ~4x step cost on v5e for BERT-base; the win is the ~800MB of
    HBM the fp32 moments no longer occupy."""
    dtype = resolve_dtype(args.dtype)
    remat = bool(args.remat)
    # "auto" flows through: ops.attention.routed_impl resolves it at trace
    # time with the batch's real shape/packedness/dropout in hand
    attn_impl = args.attention_impl
    unroll = _unroll(args)
    smoothing = args.label_smoothing
    fused_ce = resolve_fused_ce(args)

    def loss_fn(params, batch, rng):
        # aux is the MoE load-balancing loss, a constant 0 for dense models
        # (XLA folds the add away); it joins the optimized objective only —
        # the reported loss stays bare CE so MoE and dense runs read on the
        # same scale
        out, aux = bert.classify(
            params, cfg, batch, dtype=dtype, deterministic=False, rng=rng,
            remat=remat, attn_impl=attn_impl, unroll=unroll, return_aux=True,
            return_pooled=fused_ce == "pallas",
        )
        # packed rows return per-SEGMENT outputs [B, M, .] with [B, M]
        # labels/weights: flatten to the per-example stream — the weighted
        # CE below is then exactly the unpacked loss over the same
        # examples (empty slots weigh 0, like filler rows)
        labels, weights = batch["label"], batch["example_weight"]
        if out.ndim == 3:
            out = out.reshape(-1, out.shape[-1])
            labels = labels.reshape(-1)
            weights = weights.reshape(-1)
        if fused_ce == "pallas":
            # ``out`` is the pooled pre-classifier features: the kernel
            # consumes the final projection itself, so the [T, C] logits
            # never round-trip HBM (ops.fused_ce)
            loss, correct, objective = fused_weighted_ce(
                out, params["classifier"]["kernel"].astype(dtype),
                params["classifier"]["bias"].astype(dtype),
                labels, weights, smoothing=smoothing)
        else:
            loss, correct, objective = weighted_ce(
                out, labels, weights, smoothing=smoothing)
        return objective + cfg.moe_aux_coef * aux, (loss, correct)

    ema_decay = getattr(args, "ema_decay", 0.0)
    bf16_grads = dtype != jnp.float32 and getattr(args, "grads_dtype",
                                                  "param") == "compute"

    def train_step(state: State, batch: Dict[str, jax.Array]) -> Tuple[State, Metrics]:
        rng = jax.random.fold_in(state["rng"], state["step"])
        params = state["params"]
        if bf16_grads:
            # Pre-cast the big matmul kernels to the compute dtype OUTSIDE
            # the differentiated function, so their gradients are *produced*
            # in bf16 — the AMP analog of fp16 grads on the wire
            # (/root/reference/multi-gpu-distributed-mp-amp-cls.py:167-175
            # keeps fp16 grads until the unscale).  Forward math is bitwise
            # unchanged (the kernels were cast per-use inside loss_fn
            # anyway); what changes is the backward's materialization: grad
            # assembly for the [L,...]-stacked kernels (dynamic-update-slice
            # chains) moves half the bytes.  The mu/nu ACCUMULATORS stay
            # fp32, but each increment is computed from the bf16 grad (nu's
            # g**2 squares in bf16) — measured NEUTRAL to -6% on v5e and
            # non-default for that reason (record removed, not re-measured).
            params = cast_kernels(params, dtype)
        (_, (loss, correct)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng
        )
        # bf16 grads flow into the optimizer AS bf16: Adam's moment
        # arithmetic promotes them to fp32 per-element inside the fused
        # update loops (an explicit tree-wide upcast here measured as a
        # no-op — XLA pushes the convert back into the grad-assembly chain,
        # rebuilding the fp32 DUS traffic the cast exists to avoid).
        opt_in = state["opt_state"]
        if opt_staging is not None:
            opt_in = jax.device_put(opt_in, opt_staging[0])   # host -> device
        updates, opt_state = tx.update(grads, opt_in, state["params"])
        if opt_staging is not None:
            opt_state = jax.device_put(opt_state, opt_staging[1])  # -> host
        params = optax.apply_updates(state["params"], updates)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
            "rng": state["rng"],
        }
        if "ema" in state:
            # bias-corrected-free simple EMA: eval/checkpoint weights
            # (Polyak averaging — smooths the tail of the LR schedule)
            d = jnp.asarray(ema_decay, jnp.float32)
            new_state["ema"] = jax.tree_util.tree_map(
                lambda e, p: (d * e.astype(jnp.float32)
                              + (1.0 - d) * p.astype(jnp.float32)
                              ).astype(e.dtype) if hasattr(e, "dtype")
                else e,
                state["ema"], params)
        wsum = jnp.maximum(batch["example_weight"].sum(), 1.0)
        return new_state, {"loss": loss, "accuracy": correct / wsum}

    return train_step


def make_train_step(cfg: BertConfig, tx: optax.GradientTransformation, args
                    ) -> Callable[[State, Dict[str, jax.Array]], Tuple[State, Metrics]]:
    """Build the fused train step.  Strategy = where you place the inputs."""
    return jax.jit(build_train_step(cfg, tx, args), donate_argnums=0)


def build_multi_step(step_fn: Callable) -> Callable:
    """``lax.scan`` K sequential optimizer steps into ONE device program.

    Math-identical to K separate calls (same updates, in order; per-step
    metrics come back stacked ``[K]``) — what changes is dispatch: one
    host->device round trip per K steps instead of per step; the TPU twin
    of CUDA-graph step capture.  The trade-off as measured before PR 1 on
    v5e (BERT-base, batch 32; record removed, not re-measured on this
    code): scan-carried weights cost ~6% device-step speed (XLA loses some
    layout freedom), bought back wherever per-step dispatch is the larger
    term, which is why the benchmark's recipe sets ``fuse_steps=4``.  Where dispatch
    is cheap ``fuse_steps=1`` may be marginally faster — a chip cell has to
    say.
    """

    def multi_step(state: State, batches: Dict[str, jax.Array]
                   ) -> Tuple[State, Metrics]:
        return jax.lax.scan(step_fn, state, batches)

    return multi_step


def make_multi_step(cfg: BertConfig, tx: optax.GradientTransformation, args
                    ) -> Callable[[State, Dict[str, jax.Array]], Tuple[State, Metrics]]:
    """Jitted K-step fusion for single-device runs (batches: ``[K, B, ...]``)."""
    return jax.jit(build_multi_step(build_train_step(cfg, tx, args)),
                   donate_argnums=0)


def build_eval_step(cfg: BertConfig, args) -> Callable[..., Metrics]:
    """Unjitted deterministic eval step returning global sums (host
    accumulates).

    The reference's ``dev``/``test`` all-gather logits+labels across ranks
    (``multi-gpu-distributed-cls.py:145-155``); with a batch sharded over the
    mesh the same gather happens inside XLA and the returned scalars are
    already global.
    """
    dtype = resolve_dtype(args.dtype)
    attn_impl = args.attention_impl  # ops.attention routes "auto" per trace
    unroll = _unroll(args)

    def eval_step(params, batch) -> Metrics:
        logits = bert.classify(params, cfg, batch, dtype=dtype,
                               deterministic=True, attn_impl=attn_impl,
                               unroll=unroll)
        labels, w = batch["label"], batch["example_weight"]
        if logits.ndim == 3:  # packed rows: per-segment -> per-example
            logits = logits.reshape(-1, logits.shape[-1])
            labels = labels.reshape(-1)
            w = w.reshape(-1)
        loss, correct, _ = weighted_ce(logits, labels, w)
        return {
            "loss_sum": loss * jnp.maximum(w.sum(), 1.0),
            "weight": w.sum(),
            "correct": correct,
            "pred": jnp.argmax(logits, -1),
            # echo labels/weights through the device: with a sharded batch and
            # replicated outputs this is the all-gather that lets every host
            # assemble the full (pred, label) stream for the report
            # (multi-gpu-distributed-cls.py:145-155).
            "label": labels,
            "ew": w,
        }

    return eval_step


def make_eval_step(cfg: BertConfig, args) -> Callable[..., Metrics]:
    """Jitted eval step (single-device / auto-propagated sharding)."""
    return jax.jit(build_eval_step(cfg, args))
