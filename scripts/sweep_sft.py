#!/usr/bin/env python
"""Supervised-stage recipe sweep: how many sft epochs, and does restoring the
trained head at fine-tune time help?

Assumes the MLM phase-1 checkpoint already exists (pretrain-tpu.py writes
output/pretrained-mlm.msgpack when sft follows; a bare MLM artifact at
output/pretrained.msgpack works too — pass it via ``--mlm PATH``).

Positional args select grid rows by name under the exact-name rule
(``pdnlp_tpu.utils.sweeps``): ``sft3-ref1ep-head`` runs one cell,
``2ep-wl`` substring-selects the 2-epoch recipe across all sft depths.

Prints best-of-epoch dev accuracy per (sft_epochs, fine-tune recipe) cell.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pdnlp_tpu.train.pretrain import run_supervised_stage
from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.utils.config import Args, enable_compilation_cache, \
    pop_cli_flag
from pdnlp_tpu.utils.sweeps import make_selected, parse_only

enable_compilation_cache()


def finetune(tag, ckpt, **kw):
    args = Args(strategy="exp", dtype="bfloat16", init_from=ckpt,
                dev=True, eval_step=50, log_every=10 ** 9,
                ckpt_name="sweep-tmp.msgpack", **kw)
    tr, loader, dev_loader = build_parallel_trainer(args, mode="dp")
    tr.train(loader, dev_loader)
    print(f"{tag:44s} best={tr.best_accuracy:.4f}", flush=True)
    return tr.best_accuracy


def main():
    argv, mlm = pop_cli_flag(sys.argv[1:], "--mlm",
                             default="output/pretrained-mlm.msgpack")
    if argv and argv[0].endswith(".msgpack"):
        # pre-flag invocation shape: a bare checkpoint path as argv[1]
        mlm = argv.pop(0)

    grid = {}
    for sft_epochs in (1, 2, 3, 5):
        # reference's exact protocol: 1 epoch, constant 3e-5
        grid[f"sft{sft_epochs}-ref1ep-fresh"] = (sft_epochs, dict())
        grid[f"sft{sft_epochs}-ref1ep-head"] = (sft_epochs,
                                                dict(init_head=True))
        # shipped recipe: 2 epochs, linear warmup->decay
        grid[f"sft{sft_epochs}-2ep-wl-head"] = (
            sft_epochs, dict(init_head=True, epochs=2,
                             lr_schedule="warmup_linear"))

    selected = make_selected(parse_only(argv), grid)
    for name, (sft_epochs, kw) in grid.items():
        if not selected(name):
            continue
        sft_ckpt = f"output/sft-e{sft_epochs}.msgpack"
        if not os.path.exists(sft_ckpt):
            run_supervised_stage(Args(
                strategy="sft", dtype="bfloat16", init_from=mlm,
                epochs=sft_epochs, learning_rate=3e-5,
                lr_schedule="warmup_linear", dev=False,
                log_every=10 ** 9, ckpt_name=os.path.basename(sft_ckpt)))
        finetune(name, sft_ckpt, **kw)


if __name__ == "__main__":
    main()
