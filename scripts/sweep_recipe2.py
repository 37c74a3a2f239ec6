#!/usr/bin/env python
"""Round 2: schedule x epochs combinations on the best pretrain ckpt.

Positional args select rows by name under the exact-name rule
(``pdnlp_tpu.utils.sweeps``): ``2ep-wl-5e-5`` runs exactly that cell;
``wl`` substring-selects every warmup-linear row.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.sweeps import make_selected, parse_only

CKPT = "output/pretrained_p30.msgpack"


def run(tag, schedule_fn=None, **kw):
    import pdnlp_tpu.parallel.execution as ex
    import pdnlp_tpu.train.optim as optim_mod

    orig = optim_mod.build_optimizer
    if schedule_fn is not None:
        def patched(params, args, schedule=None):
            return orig(params, args, schedule=schedule_fn)
        optim_mod.build_optimizer = patched
        ex_orig = ex.build_optimizer
        ex.build_optimizer = patched
    try:
        args = Args(strategy="exp", dtype="bfloat16", init_from=CKPT,
                    dev=True, eval_step=50, log_every=10 ** 9,
                    ckpt_name="sweep-tmp.msgpack", **kw)
        tr, loader, dev_loader = build_parallel_trainer(args, mode="dp")
        tr.train(loader, dev_loader)
        print(f"{tag:30s} best={tr.best_accuracy:.4f}", flush=True)
    finally:
        if schedule_fn is not None:
            optim_mod.build_optimizer = orig
            ex.build_optimizer = ex_orig


def wl(peak, total):
    """The shipped warmup_linear schedule, built by the same helper the
    framework uses (one formula, one place: optim.make_schedule)."""
    from pdnlp_tpu.train.optim import make_schedule

    return make_schedule(Args(lr_schedule="warmup_linear",
                              learning_rate=peak), total)


def main():
    grid = {
        "2ep-wl-5e-5": dict(schedule_fn=wl(5e-5, 576), epochs=2),
        "2ep-wl-3e-5": dict(schedule_fn=wl(3e-5, 576), epochs=2),
        "3ep-wl-5e-5": dict(schedule_fn=wl(5e-5, 864), epochs=3),
        "3ep-const-3e-5": dict(epochs=3),
        "2ep-const-5e-5": dict(learning_rate=5e-5, epochs=2),
    }
    selected = make_selected(parse_only(sys.argv[1:]), grid)
    for name, kw in grid.items():
        if selected(name):
            run(name, **kw)


if __name__ == "__main__":
    main()
