#!/usr/bin/env python
"""Fine-tune recipe experiments: warmup schedule, layerwise LR decay, 2-epoch.

Runs in-process (TPU) with the best pretrain checkpoint; prints best-of-epoch
dev accuracy per recipe.

Positional args select rows by name under the exact-name rule
(``pdnlp_tpu.utils.sweeps``): ``cosine-3e-5`` runs exactly that recipe;
``cosine`` substring-selects the family.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import optax

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.sweeps import make_selected, parse_only

CKPT = "output/pretrained_p30.msgpack"


def run(tag, **kw):
    import pdnlp_tpu.train.optim as optim_mod

    schedule_fn = kw.pop("schedule_fn", None)
    orig = optim_mod.build_optimizer
    if schedule_fn is not None:
        def patched(params, args, schedule=None):
            return orig(params, args, schedule=schedule_fn)
        optim_mod.build_optimizer = patched
        # execution.py imported the symbol directly
        import pdnlp_tpu.parallel.execution as ex
        ex_orig = ex.build_optimizer
        ex.build_optimizer = patched
    try:
        args = Args(strategy="exp", dtype="bfloat16", init_from=CKPT,
                    dev=True, eval_step=50, log_every=10 ** 9,
                    ckpt_name="sweep-tmp.msgpack", **kw)
        tr, loader, dev_loader = build_parallel_trainer(args, mode="dp")
        tr.train(loader, dev_loader)
        print(f"{tag:26s} best={tr.best_accuracy:.4f}", flush=True)
    finally:
        if schedule_fn is not None:
            optim_mod.build_optimizer = orig
            ex.build_optimizer = ex_orig


TOTAL = 288


def main():
    grid = {
        "baseline-const-3e-5": dict(),
        "cosine-3e-5": dict(schedule_fn=optax.warmup_cosine_decay_schedule(
            0.0, 3e-5, warmup_steps=17, decay_steps=TOTAL)),
        "cosine-5e-5": dict(schedule_fn=optax.warmup_cosine_decay_schedule(
            0.0, 5e-5, warmup_steps=17, decay_steps=TOTAL)),
        "linear-5e-5": dict(schedule_fn=optax.join_schedules(
            [optax.linear_schedule(0.0, 5e-5, 17),
             optax.linear_schedule(5e-5, 0.0, TOTAL - 17)], [17])),
        "2ep-const-3e-5": dict(epochs=2),
    }
    selected = make_selected(parse_only(sys.argv[1:]), grid)
    for name, kw in grid.items():
        if selected(name):
            run(name, **kw)


if __name__ == "__main__":
    main()
