"""Training through the declarative ``AutoTrainer`` — the HF Trainer analog.

Capability twin of ``/root/reference/multi-gpu-transformers-cls.py``: declare
``TrainerArgs`` (step-based eval/save, bf16 instead of fp16, best-model
reload — the reference's exact knobs at ``:150-168``), call ``train()`` and
``evaluate()``, print the runtime metrics HF Trainer reports
(``train_runtime``/``train_samples_per_second``, ``script.ipynb`` cell 23).

    python multi-tpu-trainer-cls.py [--bf16 true] [--eval_steps 50]
"""
from pdnlp_tpu.train.auto import AutoTrainer, TrainerArgs
from pdnlp_tpu.utils.logging import rank0_print


def parse_trainer_args(argv=None) -> TrainerArgs:
    """Typed CLI over ``TrainerArgs`` via the shared dataclass-arg builder
    (``utils.config.add_dataclass_args`` — one Optional-unwrapping loop for
    the whole framework)."""
    import argparse

    from pdnlp_tpu.utils.config import (
        add_dataclass_args, enable_compilation_cache,
    )

    p = argparse.ArgumentParser()
    add_dataclass_args(p, TrainerArgs)
    ns, _ = p.parse_known_args(argv)
    enable_compilation_cache()
    return TrainerArgs(**vars(ns))


if __name__ == "__main__":
    trainer = AutoTrainer(parse_trainer_args())
    train_metrics = trainer.train()
    rank0_print({k: round(v, 4) for k, v in train_metrics.items()})
    eval_metrics = trainer.evaluate()
    rank0_print({k: round(v, 4) for k, v in eval_metrics.items()})
