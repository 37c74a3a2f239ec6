"""Pipeline parallelism — GPipe-style stages over a ``stage`` mesh axis.

No reference twin exists (``SURVEY.md`` §2.3: the reference's only
model-state sharding is ZeRO-3); this is a capability the TPU framework
adds, completing the parallelism quartet (data / tensor / sequence /
pipeline).  The design is TPU-idiomatic SPMD, not a multi-controller
scheduler:

- the stacked layer tree ``params['layers']`` (leading dim ``L``) shards
  its leading dim across ``stage`` — each device physically holds ``L/S``
  contiguous layers (plus replicated embeddings/head, which are small);
- one ``shard_map`` program runs the classic pipelined loop: the batch
  splits into ``M`` microbatches, and for ``M + S - 1`` ticks every stage
  runs its layer slice and ``ppermute``s activations to the next stage —
  the same single-program pipeline loop TPU pod frameworks use, with the
  (S-1)/(M+S-1) GPipe bubble;
- backward is ``jax.grad`` straight through the tick scan and the
  ``ppermute`` (whose transpose is the reverse permutation), i.e. the
  reversed pipeline, with gradients for each stage's layers landing on
  that stage and gradients for the replicated trees ``psum``-combined.

Cost note: embeddings and the pooler/classifier head are replicated, so
EVERY stage computes the full-batch embedding pass and the head (the
results are discarded on all but the first/last stage via the masked-psum
selects).  At BERT scale this is deliberate — embed+head are <2% of layer
FLOPs and replicating them keeps the tick loop free of extra collectives —
but it grows linearly with stage count; a deep-pipeline deployment would
gate them on ``axis_index`` at the price of a divergent program per stage.

Dropout note: per-layer streams key on *global* layer indices
(``bert.run_layers``), so each layer's stream is stage-placement-invariant;
the microbatch split makes the batch-level stream differ from the
single-device run, so exact-parity tests run dropout=0 (as the other
strategy-parity tests do).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pdnlp_tpu.models import bert
from pdnlp_tpu.models.config import BertConfig
from pdnlp_tpu.parallel.mesh import DATA_AXIS
from pdnlp_tpu.train.precision import resolve_dtype
from pdnlp_tpu.train.steps import init_state, weighted_ce

STAGE = "stage"
State = Dict[str, object]


def _is_layer_path(path) -> bool:
    return any(isinstance(k, jax.tree_util.DictKey) and k.key == "layers"
               for k in path)


def pp_specs(tree):
    """PartitionSpec pytree for ``shard_map``: layer-stack leaves split
    their leading (layer) dim over ``stage``; everything else replicates.
    The Adam moments inherit the rule through their mirrored tree paths."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: P(STAGE) if _is_layer_path(path) else P(), tree)


def pp_shardings(tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), pp_specs(tree))


def setup_pp_model(args, vocab_size: int, mesh: Mesh, total_steps: int = None
                   ) -> Tuple[BertConfig, optax.GradientTransformation, State, object]:
    """(cfg, tx, state, shardings) with the layer stack sharded over
    ``stage`` from init — the pipeline twin of ``setup_sharded_model``."""
    from pdnlp_tpu.models import get_config
    from pdnlp_tpu.models.config import args_overrides
    from pdnlp_tpu.train.optim import build_optimizer, make_schedule
    from pdnlp_tpu.utils.seeding import set_seed, train_key

    if STAGE not in mesh.shape:
        raise ValueError(
            f"pp needs a {STAGE!r} mesh axis; got {dict(mesh.shape)} — "
            'pass --mesh_shape \'{"stage": S}\'')
    if getattr(args, "ema_decay", 0.0) > 0:
        raise ValueError("--ema_decay runs on the jit strategies (dp/zero/"
                         "tp/ep) — the pipeline step does not maintain the "
                         "EMA tree")
    n_stages = mesh.shape[STAGE]
    cfg = get_config(args.model, vocab_size=vocab_size, num_labels=args.num_labels,
                     dropout=args.dropout, attn_dropout=args.attn_dropout,
                     **args_overrides(args))
    if cfg.num_layers % n_stages:
        raise ValueError(f"pipeline degree {n_stages} must divide num_layers "
                         f"({cfg.num_layers}) — stages hold contiguous "
                         "layer slices")
    # MoE composes with pp: expert stacks [L, E, in, out] split their
    # leading layer dim like every other layer weight, and the tick loop
    # accumulates each stage's share of the load-balancing aux (gated to
    # real ticks; psum'd over stages in the train step)
    root = set_seed(args.seed)
    init_key, _ = jax.random.split(root)
    train_rng = train_key(args.seed, getattr(args, "rng_impl", "rbg"))
    param_shapes = jax.eval_shape(lambda k: bert.init_params(k, cfg), init_key)
    tx = build_optimizer(param_shapes, args,
                         schedule=make_schedule(args, total_steps))

    def init_fn(key, rng):
        return init_state(key, cfg, tx, rng=rng, params=bert.init_params(key, cfg))

    state_shapes = jax.eval_shape(init_fn, init_key, train_rng)
    shardings = pp_shardings(state_shapes, mesh)
    state = jax.jit(init_fn, out_shardings=shardings)(init_key, train_rng)
    if getattr(args, "init_from", None):
        from pdnlp_tpu.train.pretrain import load_encoder

        params = load_encoder(args.init_from, state["params"],
                              head=getattr(args, "init_head", False))
        state["params"] = jax.device_put(params, shardings["params"])
    return cfg, tx, state, shardings


def _pp_logits(params, batch, cfg, *, n_stages: int, n_micro: int, dtype,
               deterministic: bool, rng, remat: bool, attn_impl: str,
               unroll):
    """The pipelined forward, INSIDE ``shard_map``: returns ``(logits,
    aux)`` where logits [B, num_labels] are only meaningful on the LAST
    stage (callers ``psum``-select) and ``aux`` is this STAGE's share of
    the MoE load-balancing loss (0 for dense models; callers ``psum`` over
    ``stage``).  ``params['layers']`` leaves arrive with leading dim
    ``L/S`` (this stage's slice)."""
    s = jax.lax.axis_index(STAGE)
    B = batch["label"].shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by {n_micro} microbatches")
    b = B // n_micro
    local_layers = params["layers"]
    lk = jax.tree_util.tree_leaves(local_layers)[0].shape[0]
    seq = batch["input_ids"].shape[1]
    if rng is None:
        rng = jax.random.key(0)

    # embeddings depend only on the batch, not the pipeline carry: one pass
    # over the full batch before the loop, dynamic-indexed per tick
    x_emb, rng = bert.embed(params, cfg, batch["input_ids"],
                            batch["token_type_ids"], dtype=dtype,
                            deterministic=deterministic, rng=rng)
    x_emb = x_emb.reshape(n_micro, b, seq, cfg.hidden_size)
    masks = batch["attention_mask"].reshape(n_micro, b, seq)

    def tick(carry, t):
        h_in, outs, aux_sum = carry
        # stage 0 ingests microbatch t; this stage holds microbatch t - s
        # (both clipped during fill/drain bubble ticks)
        t_in = jnp.clip(t, 0, n_micro - 1)
        x0 = jax.lax.dynamic_index_in_dim(x_emb, t_in, 0, keepdims=False)
        x = jnp.where(s == 0, x0, h_in)
        m_here = jnp.clip(t - s, 0, n_micro - 1)
        mask = jax.lax.dynamic_index_in_dim(masks, m_here, 0, keepdims=False)
        x, aux = bert.run_layers(
            local_layers, cfg, x, li=s * lk + jnp.arange(lk),
            bias=bert.mask_bias(mask, dtype), dtype=dtype,
            deterministic=deterministic,
            rng=jax.random.fold_in(rng, m_here), remat=remat,
            attn_impl=attn_impl, unroll=unroll, with_aux=True,
            token_mask=mask)
        # bubble ticks recompute a clipped microbatch whose result is
        # discarded — its aux must not count (it would double-weight the
        # edge microbatches); a real tick on this stage is 0 <= t-s < M
        real = ((t - s >= 0) & (t - s < n_micro)).astype(aux.dtype)
        aux_sum = aux_sum + aux * real
        # the last stage finishes microbatch t - (S-1) this tick; only its
        # [CLS] row feeds the head, so that is all the loop accumulates
        done = t - (n_stages - 1)
        d_idx = jnp.clip(done, 0, n_micro - 1)
        write = (s == n_stages - 1) & (done >= 0) & (done < n_micro)
        cur = jax.lax.dynamic_index_in_dim(outs, d_idx, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(write, x[:, 0, :], cur), d_idx, 0)
        h_out = jax.lax.ppermute(
            x, STAGE, [(i, (i + 1) % n_stages) for i in range(n_stages)])
        return (h_out, outs, aux_sum), None

    h0 = jnp.zeros((b, seq, cfg.hidden_size), dtype)
    outs0 = jnp.zeros((n_micro, b, cfg.hidden_size), dtype)
    (_, outs, aux_sum), _ = jax.lax.scan(
        tick, (h0, outs0, jnp.zeros((), jnp.float32)),
        jnp.arange(n_micro + n_stages - 1))

    logits = bert.pooled_logits(
        params, cfg, outs.reshape(B, cfg.hidden_size), dtype=dtype,
        drop_rng=None if deterministic else jax.random.fold_in(rng, 10_000))
    # mean over microbatches: each real tick added this stage's layer-slice
    # aux for one microbatch, so the per-microbatch mean matches the dense-
    # dispatch convention (sum over layers of batch-statistic aux) up to
    # the estimator (per-microbatch vs full-batch statistics)
    return logits, aux_sum / n_micro


def _select_last(x, n_stages: int):
    """Zero out every stage's value but the last's, then ``psum`` — the
    SPMD way to read a value that only the final pipeline stage owns."""
    s = jax.lax.axis_index(STAGE)
    on_last = (s == n_stages - 1).astype(x.dtype)
    return jax.lax.psum(x * on_last, STAGE)


def _lazy_jit(make):
    """Defer jit+shard_map construction to the first call so ``in_specs``
    can be derived from the caller's actual pytree (optax wrappers vary
    with the configured schedule)."""
    compiled = {}

    def call(first, *rest):
        if "fn" not in compiled:
            compiled["fn"] = make(first)
        return compiled["fn"](first, *rest)

    return call


def make_pp_train_step(cfg: BertConfig, tx, args, mesh: Mesh,
                       n_micro: int = 4):
    """Compile the pipelined train step.  Gradients of each stage's layer
    slice stay on that stage; gradients of the replicated trees are
    ``psum``-combined (they receive nonzero cotangents only on the stages
    that use them — embeddings on stage 0, the head on the last).

    Composes with data parallelism: on a ``(data x stage)`` mesh the batch
    arrives split along ``data``, each data shard runs its own pipeline,
    and gradients weight-combine across shards exactly as the shard_map
    (Horovod-analog) path does — the global-mean gradient stays exact even
    when filler rows make shards uneven."""
    n_stages = mesh.shape[STAGE]
    has_data = DATA_AXIS in mesh.shape
    dtype = resolve_dtype(args.dtype)
    remat = bool(args.remat)
    attn_impl = args.attention_impl  # ops.attention routes "auto" per trace
    from pdnlp_tpu.train.steps import _unroll

    unroll = _unroll(args)
    smoothing = args.label_smoothing
    batch_spec = P(DATA_AXIS) if has_data else P()

    def loss_fn(params, batch, rng):
        logits, aux = _pp_logits(params, batch, cfg, n_stages=n_stages,
                                 n_micro=n_micro, dtype=dtype,
                                 deterministic=False, rng=rng, remat=remat,
                                 attn_impl=attn_impl, unroll=unroll)
        loss, correct, objective = weighted_ce(
            logits, batch["label"], batch["example_weight"],
            smoothing=smoothing)
        # objective (smoothed + MoE aux, each stage contributing its layer
        # slice's share) is differentiated; bare CE is reported
        objective = (_select_last(objective, n_stages)
                     + cfg.moe_aux_coef * jax.lax.psum(aux, STAGE))
        return objective, (
            _select_last(loss, n_stages), _select_last(correct, n_stages))

    def per_device(state: State, batch):
        rng = jax.random.fold_in(state["rng"], state["step"])
        if has_data:  # distinct dropout stream per data shard (cf. shardmap)
            rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA_AXIS))
        (_, (loss, correct)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], batch, rng)
        if has_data:
            # local grads are weighted means over the local shard; combine
            # them weighted by local weight mass -> exact global mean
            from pdnlp_tpu.parallel.collectives import weighted_shard_scale

            scale, gw = weighted_shard_scale(
                batch["example_weight"].sum(), DATA_AXIS)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            loss = jax.lax.psum(loss * scale, DATA_AXIS)
            correct = jax.lax.psum(correct, DATA_AXIS)
        else:
            gw = jnp.maximum(batch["example_weight"].sum(), 1.0)

        def reduce_g(g, with_stage):
            axes = ((DATA_AXIS,) if has_data else ()) + \
                   ((STAGE,) if with_stage else ())
            return jax.lax.psum(g, axes) if axes else g

        grads = {k: jax.tree_util.tree_map(
                     lambda g: reduce_g(g, with_stage=(k != "layers")), v)
                 for k, v in grads.items()}
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1, "rng": state["rng"]}
        return new_state, {"loss": loss, "accuracy": correct / gw}

    return _lazy_jit(lambda state: jax.jit(
        shard_map(
            per_device, mesh=mesh,
            in_specs=(pp_specs(state), batch_spec),
            out_specs=(pp_specs(state), P()),
            check_vma=False,
        ),
        donate_argnums=0,
    ))


def make_pp_eval_step(cfg: BertConfig, args, mesh: Mesh, n_micro: int = 4):
    """Deterministic pipelined eval step with ``build_eval_step``'s metric
    contract: global scalar sums (replicated), per-row preds/labels left
    sharded along ``data`` (the host fetch is the all-gather)."""
    n_stages = mesh.shape[STAGE]
    has_data = DATA_AXIS in mesh.shape
    dtype = resolve_dtype(args.dtype)
    attn_impl = args.attention_impl  # ops.attention routes "auto" per trace
    from pdnlp_tpu.train.steps import _unroll

    unroll = _unroll(args)
    batch_spec = P(DATA_AXIS) if has_data else P()

    def data_sum(x):
        return jax.lax.psum(x, DATA_AXIS) if has_data else x

    def per_device(params, batch):
        logits, _ = _pp_logits(params, batch, cfg, n_stages=n_stages,
                               n_micro=n_micro, dtype=dtype,
                               deterministic=True, rng=None, remat=False,
                               attn_impl=attn_impl, unroll=unroll)
        w = batch["example_weight"]
        loss, correct, _ = weighted_ce(logits, batch["label"], w)
        return {
            "loss_sum": data_sum(
                _select_last(loss * jnp.maximum(w.sum(), 1.0), n_stages)),
            "weight": data_sum(w.sum()),
            "correct": data_sum(_select_last(correct, n_stages)),
            "pred": _select_last(jnp.argmax(logits, -1), n_stages),
            "label": batch["label"],
            "ew": w,
        }

    out_specs = {"loss_sum": P(), "weight": P(), "correct": P(),
                 "pred": batch_spec, "label": batch_spec, "ew": batch_spec}
    return _lazy_jit(lambda params: jax.jit(shard_map(
        per_device, mesh=mesh,
        in_specs=(pp_specs(params), batch_spec),
        out_specs=out_specs,
        check_vma=False,
    )))


def make_pp_batch(mesh: Mesh):
    """Host batch -> global arrays on the pipeline mesh: split along
    ``data`` when that axis exists (each shard runs its own pipeline),
    replicated across ``stage`` (activations, not data, flow stage to
    stage).  ``make_array_from_process_local_data`` covers both the
    single-process mesh and a mesh whose axes span processes (each host
    contributes its data shard / its replica of the full batch)."""
    spec = P(DATA_AXIS) if DATA_AXIS in mesh.shape else P()
    sh = NamedSharding(mesh, spec)

    def put(batch):
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                sh, np.asarray(a)), batch)

    return put
