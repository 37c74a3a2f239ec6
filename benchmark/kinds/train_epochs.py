"""Traffic kind ``train_epochs``: fine-tune over a seeded corpus, epoch after
epoch, for the length of the window.

The system under test is what ``train.run.build_parallel_trainer`` returns —
the compiled step programs, their state and the input pipeline — driven as
``Trainer.train`` drives them: ``pipeline.set_epoch`` / ``macro_batches``
feed ``multi_step`` (or ``train_step`` for an epoch's tail).  The benchmark
owns the loop so that the window is whole dispatches between two barriers
and so that the first dispatch's state can be compared with the reference.

Window: opens after a barrier on the last warm-up dispatch, runs whole
dispatches until the host clock passes ``--seconds``, closes with a barrier
on the last.  The host runs one dispatch ahead of the device and no more, so
the last dispatch ends within one dispatch of the clock.
"""
from __future__ import annotations

import gc
import json
import os
import statistics

from benchmark import adapters, common, counts, loadgen


def program_args(cell, ctx, sizes, wd):
    """The program's ``Args`` for this cell: the configuration's recipe, the
    traffic's data, and the files this run wrote."""
    from pdnlp_tpu.utils.config import Args

    tr, prog = cell.traffic, dict(cell.config["program"])
    if ctx.rehearse:
        prog.update(cell.rehearsal("program"))
    prog.pop("explicit_collectives", None)
    rows = tr["rows"] + tr["dev_rows"]
    return Args(
        dropout=sizes["hidden_dropout_prob"],
        attn_dropout=sizes["attention_probs_dropout_prob"],
        data_path=os.path.join(wd, "corpus.json"),
        vocab_path=os.path.join(wd, "vocab.txt"), output_dir=wd,
        max_seq_len=tr["seq_len"], data_limit=rows,
        ratio=(tr["rows"] + 0.5) / rows, length_mode=tr["length_mode"],
        pipeline=tr["pipeline"], num_labels=sizes["num_labels"],
        seed=ctx.seed % (2 ** 31 - 1), epochs=tr["recipe_epochs"],
        dev=False, log_every=10 ** 9, num_devices=cell.chips, **prog)


def write_inputs(cell, ctx, sizes, wd):
    tr = dict(cell.traffic)
    if ctx.rehearse:
        tr.update(cell.rehearsal("traffic"))
        cell.traffic = tr
    total = {**tr, "rows": tr["rows"] + tr["dev_rows"]}
    data = loadgen.corpus(total, ctx.seed, sizes["vocab_size"],
                          sizes["num_labels"])
    with open(os.path.join(wd, "corpus.json"), "w", encoding="utf-8") as f:
        json.dump([[t, l] for t, l in data], f, ensure_ascii=False)
    with open(os.path.join(wd, "vocab.txt"), "w", encoding="utf-8") as f:
        f.write("\n".join(loadgen.vocab_lines(sizes["vocab_size"])) + "\n")


def inject_weights(trainer, seed, sizes):
    """Lay the benchmark's seeded weights into the trainer's state: made on
    the device, in the state's own shardings, AFTER the program's own
    initial weights are dropped, so that the state never holds two sets."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import weights

    state = trainer.state
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state["params"])
    sh = jax.tree_util.tree_map(lambda x: x.sharding, like)
    had_ema = state.pop("ema", None) is not None
    state["params"] = None
    tree = weights.make_weights(seed, sizes, layout=adapters.to_program_params,
                                out_shardings=sh)
    adapters.check_same_tree(tree, like, "parameter")
    state["params"] = tree
    if had_ema:
        state["ema"] = jax.tree_util.tree_map(jnp.copy, tree)
    return sh


def _norm_fns():
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    norms = jax.jit(lambda t: jax.tree_util.tree_map(norm, t))

    def delta(params, seed, sizes):
        """Norm of each leaf's change from the seeded weights, which are made
        again inside the call and never held beside the state."""
        from benchmark.reference import weights

        def f(params, key):
            start = adapters.to_program_params(weights.generate(key, sizes))
            return jax.tree_util.tree_map(
                lambda x, y: norm(x.astype(jnp.float32) - y), params, start)

        return jax.jit(f)(params, weights.seed_key(seed))

    return norms, delta


def run(cell, ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pdnlp_tpu.train.run import build_parallel_trainer

    sizes = cell.sizes(ctx.rehearse)
    wd = common.work_dir(cell.name)
    write_inputs(cell, ctx, sizes, wd)
    tr = cell.traffic
    args = program_args(cell, ctx, sizes, wd)
    trainer, train_loader, _ = build_parallel_trainer(
        args, mode=args.strategy, explicit_collectives=bool(
            cell.config["program"].get("explicit_collectives", False)))
    inject_weights(trainer, ctx.seed, sizes)
    fuse = args.fuse_steps if trainer.multi_step is not None else 1
    pipe = trainer.pipeline
    rows, seq = pipe.rows if hasattr(pipe, "rows") else None, tr["seq_len"]
    acc = jax.jit(lambda c, m: c + jnp.sum(m, dtype=jnp.int32))
    norms, delta = _norm_fns()

    def groups_of(epoch):
        pipe.set_epoch(epoch)
        return iter(pipe.macro_batches(fuse))

    def dispatch(batch, fused):
        step = trainer.multi_step if fused else trainer.train_step
        trainer.state, m = step(trainer.state, batch)
        return m["loss"]

    # ---- warm-up: epoch 0.  The first group runs and is the one the
    # reference follows; after it, one group of every other kind the epoch
    # holds (a tail of single steps) runs once; the rest are fed, not run.
    seen, first, loss = set(), None, None
    for batch, n, fused, _ in groups_of(0):
        kind = (bool(fused), tuple(batch["input_ids"].shape))
        if kind in seen:
            continue
        seen.add(kind)
        if first is None:
            host = {k: np.asarray(jax.device_get(v)) for k, v in batch.items()}
            loss = dispatch(batch, fused)
            mu = optax.tree_utils.tree_get(trainer.state["opt_state"], "mu")
            first = {
                "batch": host, "n": n,
                "losses": [float(x) for x in np.atleast_1d(
                    np.asarray(jax.device_get(loss)))],
                "mu": adapters.from_program_params(jax.device_get(norms(mu))),
                "delta": adapters.from_program_params(jax.device_get(
                    delta(trainer.state["params"], ctx.seed, sizes))),
            }
            del mu
        else:
            loss = dispatch(batch, fused)
    real = acc(jnp.zeros((), jnp.int32), jnp.zeros((1,), jnp.int32))
    epoch = 1
    groups = groups_of(epoch)
    jax.block_until_ready((loss, trainer.state["params"], real))
    gc.collect()
    gc.freeze()

    # ---- the window
    seconds = min(ctx.seconds, tr["trace_seconds"]) if ctx.trace else ctx.seconds
    note = ctx.annotate
    tracing = ctx.start_trace()
    steps = slots = dispatches = 0
    data_wait = dispatch_s = 0.0
    stamps, prev = [], None
    setup_s = common.process_age_s()
    t_open = common.now()
    while True:
        t_a = common.now()
        with note("data_wait"):
            nxt = next(groups, None)
            if nxt is None:
                epoch += 1
                groups = groups_of(epoch)
                nxt = next(groups)
        batch, n, fused, _ = nxt
        t_b = common.now()
        with note("step_dispatch"):
            loss = dispatch(batch, fused)
            real = acc(real, batch["attention_mask"])
        t_c = common.now()
        data_wait += t_b - t_a
        dispatch_s += t_c - t_b
        steps += n
        slots += int(np.prod(batch["input_ids"].shape))
        dispatches += 1
        if prev is not None:
            with note("device_block"):
                prev.block_until_ready()
            stamps.append(common.now())
        prev = loss
        if common.now() - t_open >= seconds:
            break
    with note("device_block"):
        jax.block_until_ready((loss, real))
    t_close = common.now()
    stamps.append(t_close)
    window = t_close - t_open
    trace = ctx.stop_trace(tracing)
    gc.unfreeze()
    real_tokens = int(real)
    peak = common.memory_peak_bytes(ctx.devices)
    gaps = [round((b - a) * 1e3, 3) for a, b in zip([t_open] + stamps, stamps)]
    common.say({"dispatch_ms": gaps, "steps_per_dispatch": fuse,
                "window_s": window, "epochs_entered": epoch})

    # ---- correct?  After the program's state is freed.
    recipe = dict(cell.config["recipe"])
    recipe["total_steps"] = len(train_loader) * args.epochs
    recipe["dropout"] = {
        "seed": args.seed, "impl": args.rng_impl,
        "rates": (sizes["hidden_dropout_prob"],
                  sizes["attention_probs_dropout_prob"])}
    del trainer, train_loader, pipe, groups, batch, nxt, prev, loss
    gc.collect()
    checks = compare(first, ctx.seed, sizes, recipe, cell.config["check"])
    checks.emit()

    obs = {
        "counters": {
            "steps": steps, "dispatches": dispatches, "slot_tokens": slots,
            "real_tokens": real_tokens, "window_s": window,
            "data_wait_s": data_wait, "dispatch_s": dispatch_s,
            "memory_peak_bytes": peak, "chips": cell.chips,
            "step_flops": counts.train_step_flops(
                sizes, first["batch"]["input_ids"].shape[-2], seq),
        },
        "samples": {"dispatch_ms": gaps},
        "trace": trace, "sizes": sizes, "peaks": ctx.peaks,
    }
    return {"checks": checks.rows, "correct": checks.correct, "attempted": dispatches, "failed": 0,
            "end_to_end": {"train_tokens_per_s": real_tokens / window,
                           "setup_s": setup_s},
            "obs": obs, "memory_peak_bytes": peak}


def compare(first, seed, sizes, recipe, limits, prec="f32") -> common.Checks:
    """The first dispatch against the reference that follows the same steps
    on the same rows from the same seeded weights."""
    from benchmark.reference import model, weights

    w = weights.make_weights(seed, sizes)
    n = first["n"]
    host = first["batch"]
    batches = [{k: (v[i] if n > 1 else v) for k, v in host.items()}
               for i in range(n)] if host["input_ids"].ndim == 3 else [host]
    ref_losses, ref_mu, ref_p = model.train_steps(
        w, batches, recipe, heads=sizes["num_attention_heads"],
        eps=sizes["layer_norm_eps"], prec=prec,
        dropout=recipe.get("dropout"))
    ref_mu_n = model.leaf_norms(ref_mu)
    ref_d_n = model.leaf_norms({k: ref_p[k] - w[k] for k in ref_p})
    checks = common.Checks()
    checks.add("loss_abs", max(abs(a - b) for a, b in
                               zip(first["losses"], ref_losses)),
               limits["loss_abs"],
               f"per-step loss, program {first['losses']} reference {ref_losses}")
    for name, prog, ref in (("moment_rel", first["mu"], ref_mu_n),
                            ("delta_rel", first["delta"], ref_d_n)):
        worst, leaf = worst_leaf(prog, ref)
        checks.add(name, worst, limits[name],
                   f"worst leaf {leaf}: program {float(prog[leaf]):.6g} "
                   f"reference {ref[leaf]:.6g}")
    return checks


def worst_leaf(prog: dict, ref: dict):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst, leaf = -1.0, None
    for k, r in ref.items():
        gap = abs(float(prog[k]) - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf
