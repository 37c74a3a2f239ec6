"""Offline evaluation sweep — the ``test.py`` analog.

Capability twin of ``/root/reference/test.py:85-94,144-170``: discover every
strategy checkpoint under ``--output_dir``, load each into a bare model (no
wrapper-prefix stripping needed — pytree keys never grow a ``module.``
prefix, the problem ``test.py:96-101`` works around), evaluate on the dev
split, and print a per-class classification report per checkpoint.

Reference quirk NOT replicated (documented in ``SURVEY.md`` §3.4): the
reference's ``test.py`` forgets ``set_seed`` so its eval split differs from
the training-time dev split.  Here the split is seeded identically to
training, so the report is computed on the true held-out dev set.

    python test_tpu.py [--output_dir output] [--dtype bfloat16]
"""
from __future__ import annotations

import glob
import os

import jax

from pdnlp_tpu.data.corpus import LABELS
from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.train import make_eval_step, setup_data, setup_model
from pdnlp_tpu.train.trainer import Trainer
from pdnlp_tpu.utils.config import Args, parse_cli
from pdnlp_tpu.utils.logging import rank0_print
from pdnlp_tpu.utils.metrics import classification_report


def discover_checkpoints(output_dir: str):
    """Every strategy checkpoint, sorted by name (the ``models`` dict sweep,
    ``test.py:85-94``).  Recurses one managed-run layout deep so
    ``AutoTrainer``'s ``auto/checkpoint-<step>/model.msgpack`` rotation dirs
    are swept too; pretrain-stage artifacts (``pretrained*.msgpack`` — the
    MLM encoder, and the supervised-stage output whose classifier saw only
    the held-out externals, never the protocol's train split) are not
    strategy checkpoints and are excluded."""
    return sorted(glob.glob(os.path.join(output_dir, "*-cls.msgpack"))
                  + glob.glob(os.path.join(output_dir, "model.msgpack"))
                  + glob.glob(os.path.join(output_dir, "*", "model.msgpack"))
                  + glob.glob(os.path.join(output_dir, "*", "checkpoint-*",
                                           "model.msgpack")))


def main(args: Args) -> dict:
    _, dev_loader, tok = setup_data(args)
    cfg, _, state = setup_model(args, tok.vocab_size)
    eval_step = make_eval_step(cfg, args)
    paths = discover_checkpoints(args.output_dir)
    if not paths:
        rank0_print(f"no checkpoints under {args.output_dir}/ "
                    "(run a training entrypoint first)")
        return {}
    results = {}
    for path in paths:
        name = os.path.relpath(path, args.output_dir)
        rank0_print(f"\n======== {name} ========")
        try:
            loaded = ckpt.load_params(path, state["params"])
        except Exception as e:  # e.g. a checkpoint from a different --model
            rank0_print(f"skipped (incompatible with --model {args.model}): "
                        f"{type(e).__name__}: {e}")
            continue
        # one transfer to device; otherwise every eval step re-uploads the
        # full host-numpy tree (~360MB for bert-base)
        state["params"] = jax.device_put(loaded)
        trainer = Trainer(args, cfg, state, train_step=None, eval_step=eval_step)
        r = trainer.test(dev_loader)
        rank0_print(f"test loss：{r['loss']:.6f} accuracy：{r['accuracy']:.4f}")
        rank0_print(classification_report(r["y_true"], r["y_pred"], LABELS))
        results[name] = r["accuracy"]
    return results


if __name__ == "__main__":
    main(parse_cli(base=Args()))
