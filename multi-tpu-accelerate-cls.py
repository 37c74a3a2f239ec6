"""Training through the ``Accelerator`` convenience API — the HF Accelerate
analog.

Capability twin of ``/root/reference/multi-gpu-accelerate-cls.py``: the
training loop below is written the way that script writes it — a local
``Trainer`` class with ``on_step``/``train``/``dev`` built by the *user*,
single-device style — and becomes distributed only through the three
``Accelerator`` calls (``prepare``, ``compile_step``, ``compile_eval``),
mirroring ``accelerator.prepare(model, optimizer, train_loader, dev_loader)``
(``:289-294``).  Note ``total_step`` is the *global* step count, already
divided by the device count via the re-batched loader — the reference
highlights this division at ``:145,271``.

    python multi-tpu-accelerate-cls.py [--dtype bfloat16]
"""
import time

from pdnlp_tpu.data.corpus import LABELS
from pdnlp_tpu.train import setup_data, setup_model
from pdnlp_tpu.train.accel import Accelerator
from pdnlp_tpu.train.steps import build_eval_step, build_train_step
from pdnlp_tpu.utils.config import Args, parse_cli
from pdnlp_tpu.utils.logging import fmt_elapsed_minutes, fmt_train
from pdnlp_tpu.utils.metrics import classification_report


def main(args: Args) -> float:
    if args.accel_config:
        # machine config as a FILE (the reference ships default_config.yaml
        # and feeds it via `accelerate launch --config_file`): mesh shape /
        # precision / rendezvous come from the file, CLI args fill the rest
        accelerator = Accelerator.from_config(args.accel_config, args=args)
        args = accelerator.args
    else:
        accelerator = Accelerator(args)

    # user-style single-device setup (the reference's main() body).
    # total_steps for the LR schedule must reflect the POST-prepare() loader:
    # prepare scales batches by accelerator.batch_mult AND reshards the
    # sampler across processes, shrinking the step count by both factors
    # (the same division the reference highlights at :145,271).
    import jax

    train_loader, dev_loader, tok = setup_data(args)
    per_process_batch = args.train_batch_size * accelerator.batch_mult
    per_process_n = -(-len(train_loader.sampler) // jax.process_count())
    steps_per_epoch = -(-per_process_n // per_process_batch)
    cfg, tx, state = setup_model(args, tok.vocab_size,
                                 total_steps=steps_per_epoch * args.epochs)

    # the one distributed-awareness step
    state, train_loader, dev_loader = accelerator.prepare(
        state, train_loader, dev_loader)
    train_step = accelerator.compile_step(build_train_step(cfg, tx, args))
    eval_step = accelerator.compile_eval(build_eval_step(cfg, args))

    total_step = len(train_loader) * args.epochs
    accelerator.print(f"devices: {accelerator.num_devices}  "
                      f"steps/epoch: {len(train_loader)}")
    wb = (next(iter(train_loader), None)
          if (args.warmup_compile or args.probe_steps) else None)
    if args.warmup_compile and wb is not None \
            and hasattr(train_step, "lower"):
        # AOT compile outside the timer (bench methodology; the prepared
        # loader already yields device-ready batches)
        train_step.lower(state, wb).compile()
    if args.probe_steps:
        # the controlled hot-loop rate (the probe), user-
        # style: re-fed steps on a state copy — train_step donates its
        # argument, so the copy keeps the real state's buffers alive
        import jax.numpy as jnp

        if wb is not None:
            pstate = jax.tree_util.tree_map(jnp.copy, state)
            for _ in range(3):
                pstate, pmet = train_step(pstate, wb)
            float(accelerator.gather(pmet["loss"]))
            t0 = time.time()
            for _ in range(args.probe_steps):
                pstate, pmet = train_step(pstate, wb)
            float(accelerator.gather(pmet["loss"]))
            accelerator.print(
                f"probe steps/s：{args.probe_steps / (time.time() - t0):.2f}")
            del pstate, pmet
    start = time.time()
    gstep = 0
    metrics = None
    pending = None  # (epoch, gstep, loss): print the PREVIOUS line's loss —
    #                 it is done by now, so the float() never stalls the
    #                 device queue (the Trainer's async-logging treatment,
    #                 applied to this user-written loop)
    for epoch in range(1, args.epochs + 1):
        train_loader.set_epoch(epoch - 1)
        for batch in train_loader:
            state, metrics = train_step(state, batch)
            gstep += 1
            if gstep % args.log_every == 0:
                if pending is not None:
                    e, s, loss = pending
                    accelerator.print(fmt_train(
                        e, args.epochs, s, total_step,
                        float(accelerator.gather(loss))))
                pending = (epoch, gstep, metrics["loss"])
    if pending is not None:
        e, s, loss = pending
        accelerator.print(fmt_train(e, args.epochs, s, total_step,
                                    float(accelerator.gather(loss))))
    if metrics is not None:
        float(accelerator.gather(metrics["loss"]))  # completion barrier
    minutes = (time.time() - start) / 60
    accelerator.print(fmt_elapsed_minutes(minutes))

    # user-style eval loop over the prepared dev loader
    y_true, y_pred = [], []
    loss_sum = weight = correct = 0.0
    for batch in dev_loader:
        m = accelerator.gather(eval_step(state["params"], batch))
        loss_sum += float(m["loss_sum"])
        weight += float(m["weight"])
        correct += float(m["correct"])
        real = m["ew"] > 0
        y_pred.extend(m["pred"][real].tolist())
        y_true.extend(m["label"][real].tolist())
    weight = max(weight, 1.0)
    accelerator.print(f"test loss：{loss_sum / weight:.6f} "
                      f"accuracy：{correct / weight:.4f}")
    accelerator.print(classification_report(y_true, y_pred, LABELS))

    from pdnlp_tpu.train import checkpoint as ckpt

    # all processes enter (consolidate is collective); rank 0 writes
    ckpt.save_params(args.ckpt_path(), state)
    return minutes


if __name__ == "__main__":
    main(parse_cli(base=Args(strategy="accelerate")))
