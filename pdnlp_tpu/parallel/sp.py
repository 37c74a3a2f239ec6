"""Sequence parallelism — training over a ``('data', 'seq')`` mesh.

The long-context path: activations are sharded along the *sequence* inside
each data shard, attention runs as ring attention (``ops.ring``), and the
classifier head is computed from the psum-broadcast [CLS] vector.  The
gradient-correctness subtlety is the redundant head compute: every seq
shard produces identical logits, so the loss is *gated to seq-shard 0* —
its backward broadcasts the pooled cotangent to all shards through the
psum, each shard backpropagates exactly its own sequence slice, and the
plain ``psum`` of gradients over ``seq`` counts head parameters once.

This capability has no reference twin (``SURVEY.md`` §5: long-context
"absent"); it exists so the framework scales past single-device sequence
lengths.  The full dropout recipe applies — hidden-state dropout per
shard and attention-probability dropout per ring block (``ops.ring``) —
so sp trains the same model as every other strategy.  Its speed at the
lengths it exists for was measured before PR 1 on v5e (record removed, not
re-measured on this code); multi-shard parity is pinned by
``tests/test_sp.py``, the multichip dryrun, and a seq axis spanning two
real OS processes in ``tests/test_spawn.py``.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pdnlp_tpu.models import BertConfig, bert
from pdnlp_tpu.train.precision import resolve_dtype
from pdnlp_tpu.train.steps import State, weighted_ce

DATA, SEQ = "data", "seq"

#: [B, S] per-TOKEN channels shard along the sequence axis; everything
#: else — flat [B] labels and the packed rows' per-SEGMENT [B, M] channels
#: (``cls_positions``/``label``/``example_weight``, whose second dim is
#: the segment slot count, not the sequence) — shards over data only.
#: One definition shared by batch placement and the step in_specs, so a
#: packed channel can never be sharded one way on upload and another in
#: the program.
TOKEN_KEYS = ("input_ids", "attention_mask", "token_type_ids",
              "segment_ids", "position_ids")


def sp_spec(key: str, val) -> P:
    """The PartitionSpec for one batch channel on the (data, seq) mesh."""
    return P(DATA, SEQ) if (getattr(val, "ndim", 0) == 2
                            and key in TOKEN_KEYS) else P(DATA)


def _flat_ce(logits, labels, weights, smoothing: float = 0.0):
    """``weighted_ce`` over packed ([B, M, C] / [B, M]) or flat inputs —
    per-segment outputs flatten to the per-example stream exactly as
    ``train.steps.build_train_step`` does, so sp's packed loss IS the
    single-device packed loss."""
    if logits.ndim == 3:
        logits = logits.reshape(-1, logits.shape[-1])
        labels = labels.reshape(-1)
        weights = weights.reshape(-1)
    return weighted_ce(logits, labels, weights, smoothing=smoothing), weights


def make_sp_batch(mesh: Mesh) -> Callable[[Dict], Dict[str, jax.Array]]:
    """Batch placement: token arrays [B, S] shard over (data, seq); label
    vectors [B] shard over data only.

    When the ``seq`` axis spans OS processes (spawn ``--mode sp``), each
    process holds the full [B, S] host batch (the data axis is then
    process-local — ``run.build_sp_trainer`` feeds accordingly) and
    ``make_array_from_callback`` hands every device exactly its sequence
    slice; ``make_array_from_process_local_data`` would instead interpret
    the full batch as this process's *shard* and mis-assemble."""
    from pdnlp_tpu.parallel.mesh import local_data_extent

    seq_spans_processes = (jax.process_count() > 1
                           and SEQ in mesh.shape
                           and local_data_extent(mesh, SEQ)[0] > 1)

    def put(batch: Dict) -> Dict[str, jax.Array]:
        out = {}
        for key, val in batch.items():
            sh = NamedSharding(mesh, sp_spec(key, val))
            if seq_spans_processes:
                out[key] = jax.make_array_from_callback(
                    val.shape, sh, lambda idx, v=val: v[idx])
            else:
                out[key] = jax.make_array_from_process_local_data(sh, val)
        return out

    return put


def make_sp_train_step(cfg: BertConfig, tx, args, mesh: Mesh):
    """Fused sequence-parallel train step (state replicated, batch sharded
    over (data, seq)); same Trainer contract as every other strategy."""
    from pdnlp_tpu.train.steps import _unroll

    dtype = resolve_dtype(args.dtype)
    remat = bool(args.remat)
    unroll = _unroll(args)
    smoothing = args.label_smoothing
    if getattr(args, "ema_decay", 0.0) > 0:
        raise ValueError("--ema_decay runs on the jit strategies (dp/zero/"
                         "tp/ep) — the sequence-parallel step does not "
                         "maintain the EMA tree")

    def local_loss(params, batch, rng):
        logits = bert.classify(params, cfg, batch, dtype=dtype,
                               deterministic=False, rng=rng, remat=remat,
                               seq_axis=SEQ, unroll=unroll)
        (loss, correct, objective), w = _flat_ce(
            logits, batch["label"], batch["example_weight"],
            smoothing=smoothing)
        # gate to seq-shard 0: head grads counted once; encoder grads flow
        # to every shard through the psum backward (see module docstring).
        # objective (smoothed) is differentiated; bare CE is reported.
        on0 = (jax.lax.axis_index(SEQ) == 0).astype(loss.dtype)
        return objective * on0, (loss * on0, correct * on0,
                                 w.sum() * on0)

    def per_device(state: State, batch) -> Tuple[State, Dict[str, jax.Array]]:
        rng = jax.random.fold_in(state["rng"], state["step"])
        rng = jax.random.fold_in(rng, jax.lax.axis_index(DATA))
        rng = jax.random.fold_in(rng, jax.lax.axis_index(SEQ))
        (_, (loss, correct, lw)), grads = jax.value_and_grad(
            local_loss, has_aux=True)(state["params"], batch, rng)
        # seq axis: plain sum (loss gated to one shard; each shard owns its
        # slice of encoder grads).  data axis: weight-mass average, exactly
        # as the explicit-collectives DP step.
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, SEQ), grads)
        loss = jax.lax.psum(loss, SEQ)
        correct = jax.lax.psum(correct, SEQ)
        lw = jax.lax.psum(lw, SEQ)
        # max(·, 1) guard matches steps.build_train_step: an all-filler
        # global batch must yield 0 loss/grads, not 0/0 NaN.
        gw = jnp.maximum(jax.lax.psum(lw, DATA), 1.0)
        scale = lw / gw
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g * scale, DATA), grads)
        loss = jax.lax.psum(loss * scale, DATA)
        acc = jax.lax.psum(correct, DATA) / gw
        updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        new_state = {"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1, "rng": state["rng"]}
        return new_state, {"loss": loss, "accuracy": acc}

    def specs_for(batch):
        return {k: sp_spec(k, v) for k, v in batch.items()}

    def compile_step(example_batch):
        mapped = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), specs_for(example_batch)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=0)

    return compile_step


def make_sp_eval_step(cfg: BertConfig, args, mesh: Mesh):
    """Deterministic sequence-parallel eval step (same metric contract as
    ``train.steps.build_eval_step``)."""
    from pdnlp_tpu.train.steps import _unroll

    dtype = resolve_dtype(args.dtype)
    unroll = _unroll(args)

    def per_device(params, batch):
        logits = bert.classify(params, cfg, batch, dtype=dtype,
                               deterministic=True, seq_axis=SEQ,
                               unroll=unroll)
        (loss, correct, _), w = _flat_ce(logits, batch["label"],
                                         batch["example_weight"])
        wsum = w.sum()
        out = {
            "loss_sum": jax.lax.psum(loss * wsum, DATA),
            "weight": jax.lax.psum(wsum, DATA),
            "correct": jax.lax.psum(correct, DATA),
            "pred": jax.lax.all_gather(jnp.argmax(logits, -1), DATA, tiled=True),
            "label": jax.lax.all_gather(batch["label"], DATA, tiled=True),
            "ew": jax.lax.all_gather(w, DATA, tiled=True),
        }
        return out

    def specs_for(batch):
        return {k: sp_spec(k, v) for k, v in batch.items()}

    def compile_step(example_batch):
        mapped = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), specs_for(example_batch)),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(mapped)

    return compile_step
