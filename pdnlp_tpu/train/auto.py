"""Declarative managed trainer — the HF ``Trainer``/``TrainingArguments``
analog.

Capability twin of ``/root/reference/multi-gpu-transformers-cls.py:150-184``:
the user states *what* they want in a frozen ``TrainerArgs`` (step-based
eval/save cadence, precision, best-model tracking, seed) and ``AutoTrainer``
owns the whole run: loop, eval every ``eval_steps``, a rotating
``checkpoint-<step>`` directory per save (``save_steps``/``save_total_limit``),
``load_best_model_at_end`` with ``metric_for_best_model``, and a
``compute_metrics`` hook (``:91-96``).  Parallelism is the framework's mesh
DP — the analog of HF Trainer's implicit DDP — plus ``mode="zero"`` for
fully-sharded, a knob HF Trainer delegates to DeepSpeed.

Resume (HF's ``resume_from_checkpoint``): ``save_optimizer_state=True``
writes a full train state per rotation dir and
``resume_from_checkpoint="<dir>"|"latest"`` continues bitwise from it
(params + Adam moments + step + RNG restored, seeded data order
fast-forwarded).  Best-model tracking survives the crash too: each
resumable save writes a ``trainer_state.json`` (HF's file of the same
name) and resume restores ``best_metric``/``best_ckpt`` from it, so a
post-resume run that never beats the pre-crash best still ships it.

The training LOOP itself lives in ``Trainer.train`` — this class only
supplies managed-cadence callbacks (``LoopHooks``); see ``train()``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.logging import rank0_print


@dataclasses.dataclass(frozen=True)
class TrainerArgs:
    """The ``TrainingArguments`` twin (reference fields at
    ``multi-gpu-transformers-cls.py:150-168``)."""

    output_dir: str = "output/auto"
    num_train_epochs: int = 1
    per_device_train_batch_size: int = 32
    per_device_eval_batch_size: int = 32
    learning_rate: float = 3e-5
    weight_decay: float = 0.01
    eval_steps: int = 50                  # evaluation_strategy="steps"
    save_steps: int = 50
    save_total_limit: Optional[int] = 3
    logging_steps: int = 10
    bf16: bool = False                    # fp16=True analog
    seed: int = 123
    load_best_model_at_end: bool = True
    metric_for_best_model: str = "accuracy"
    greater_is_better: bool = True
    # K optimizer steps fused into one device dispatch (lax.scan —
    # math-identical, per-step losses come back stacked), the same
    # fuse_steps knob the other strategies expose.  Must divide
    # logging/eval/save steps so every cadence boundary falls on a fused-
    # group boundary.  The win is where per-step dispatch dominates the
    # epoch.
    fuse_steps: int = 1
    # Rotation checkpoints are cast to this dtype ON DEVICE before the
    # fetch: "bfloat16" halves both the device->host bytes (which every
    # save at save_steps=50 pays) and the disk bytes, the analog of HF
    # Trainer's fp16 checkpoint files.  The
    # final/best model is NOT affected: a full-precision copy of the best
    # params is kept in HBM, adopted at the end, and re-written over the
    # best step's rotation dir (once, outside ``train_runtime``), so both
    # ``load_best_model_at_end`` AND the on-disk best artifact that
    # ``test_tpu.py`` sweeps are exact — only non-best rotation saves
    # (crash recovery points) stay bf16-rounded.
    save_dtype: str = "bfloat16"
    # HF's resume story: save_optimizer_state=True additionally writes
    # train_state.msgpack (params + Adam moments + step + RNG, full
    # precision — the analog of HF's optimizer.pt/scheduler.pt/rng_state)
    # into each rotation dir, and resume_from_checkpoint="<dir>" (or
    # "latest") restores it and fast-forwards the seeded data order to the
    # saved step — a bitwise continuation, like the elastic launcher's.
    # Off by default: it doubles the per-save device fetch, which dominates
    # the epoch on high-RTT transports (see save_dtype above).
    save_optimizer_state: bool = False
    resume_from_checkpoint: Optional[str] = None
    mode: str = "dp"                      # "zero" = the DeepSpeed delegation
    model: str = "bert-base"
    init_from: Optional[str] = None       # model_name_or_path analog (pretrain ckpt)
    init_head: bool = False               # restore the supervised-stage head too
    data_path: str = "/root/reference/data/train.json"
    data_limit: int = 10_000
    max_seq_len: int = 128

    def to_args(self) -> Args:
        return Args(
            strategy=f"auto-{self.mode}",
            model=self.model,
            data_path=self.data_path,
            output_dir=self.output_dir,
            train_batch_size=self.per_device_train_batch_size,
            dev_batch_size=self.per_device_eval_batch_size,
            learning_rate=self.learning_rate,
            weight_decay=self.weight_decay,
            epochs=self.num_train_epochs,
            seed=self.seed,
            eval_step=self.eval_steps,
            log_every=self.logging_steps,
            dtype="bfloat16" if self.bf16 else "float32",
            data_limit=self.data_limit,
            max_seq_len=self.max_seq_len,
            init_from=self.init_from,
            init_head=self.init_head,
            fuse_steps=self.fuse_steps,
            # the shared loop gates in-loop eval on dev, and the managed
            # runtime is reported against a warm compile (HF runs sit on a
            # warm CUDA context the same way)
            dev=True,
            warmup_compile=True,
        )


def _cast_like(params, dtype_name: str):
    """Device-side copy of a params tree with float leaves cast to
    ``dtype_name`` ("float32" = plain copy).  The cast runs on device, so a
    bf16 rotation save moves half the bytes over the device transport."""
    if dtype_name not in ("bfloat16", "float32"):
        raise ValueError(
            f"save_dtype={dtype_name!r} — use 'bfloat16' (half-byte "
            "rotation saves) or 'float32'; a silent fallback would quietly "
            "forfeit the transport/disk savings the knob exists for")
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    def leaf(x):
        if jnp.issubdtype(getattr(x, "dtype", np.float32), jnp.floating) \
                and getattr(x, "dtype", None) != dtype:
            return jnp.asarray(x, dtype)
        # same dtype: explicit copy — asarray would alias the live buffer,
        # which the next train step donates away
        return jnp.copy(x)

    return jax.tree_util.tree_map(leaf, params)


def default_compute_metrics(preds: np.ndarray, labels: np.ndarray) -> Dict[str, float]:
    """The reference's ``compute_metrics`` (argmax accuracy, ``:91-96``)."""
    return {"accuracy": float((preds == labels).mean()) if len(labels) else 0.0}


class AutoTrainer:
    """Fully-managed: ``AutoTrainer(targs).train()`` then ``.evaluate()``."""

    def __init__(self, targs: TrainerArgs,
                 compute_metrics: Callable[..., Dict[str, float]] = None):
        from pdnlp_tpu.train.run import build_parallel_trainer

        if targs.fuse_steps > 1:
            for name in ("logging_steps", "eval_steps", "save_steps"):
                if getattr(targs, name) % targs.fuse_steps:
                    raise ValueError(
                        f"fuse_steps={targs.fuse_steps} must divide {name}="
                        f"{getattr(targs, name)} — cadence boundaries must "
                        "fall on fused-group boundaries")
        self.targs = targs
        self.args = targs.to_args()
        self.compute_metrics = compute_metrics or default_compute_metrics
        self._trainer, self.train_loader, self.dev_loader = build_parallel_trainer(
            self.args, mode=targs.mode)
        self.state_history: List[Tuple[int, str]] = []  # (step, ckpt_dir)
        if targs.resume_from_checkpoint and not targs.save_optimizer_state \
                and targs.save_total_limit is not None:
            raise ValueError(
                "resume_from_checkpoint with params-only rotation saves "
                "would rotate away the pre-crash train_state.msgpack dirs — "
                "the run's ONLY recovery points if it crashes again.  Pass "
                "save_optimizer_state=True (keep writing resumable "
                "checkpoints) or save_total_limit=None (never rotate)")
        if targs.resume_from_checkpoint:
            # adopt the pre-crash rotation dirs so save_total_limit keeps
            # bounding TOTAL disk across crash/resume cycles (HF scans the
            # on-disk dirs the same way)
            import glob
            import re as _re

            for d in glob.glob(os.path.join(targs.output_dir, "checkpoint-*")):
                m = _re.fullmatch(r"checkpoint-(\d+)", os.path.basename(d))
                if m:
                    self.state_history.append((int(m.group(1)), d))
            self.state_history.sort()
        self.best_metric: Optional[float] = None
        self.best_ckpt: Optional[str] = None
        self._best_params = None  # full-precision best copy, device-held
        self._writers: List[threading.Thread] = []  # in-flight async saves
        self._writer_errors: List[Tuple[str, BaseException]] = []

    # ---------------------------------------------------------------- train
    def train(self) -> Dict[str, float]:
        """Managed run, driven by the ONE loop in ``Trainer.train``: this
        method only supplies the managed cadence callbacks (HF-style log
        line, eval-and-track-best, rotation checkpointing) via ``LoopHooks``
        — the epoch/fused-group machinery, elastic fast-forward, fused-
        boundary guard, heartbeat and profiler all come from the shared
        driver instead of a second copy of it."""
        from pdnlp_tpu.train.trainer import LoopHooks

        t = self._trainer
        targs = self.targs
        start_step = 0
        if targs.resume_from_checkpoint:
            state_path = self._resolve_resume(targs.resume_from_checkpoint)
            t.load_resume(state_path)
            start_step = int(jax.device_get(t.state["step"]))
            rank0_print(f"resumed from {state_path} at step {start_step}")
            # HF restores best-model tracking from trainer_state.json; so do
            # we — without it a resumed run whose post-resume evals never
            # beat the pre-crash best would silently ship a worse final
            # model (and rotation could delete the pre-crash best dir)
            self._restore_trainer_state(os.path.dirname(state_path))
        hooks = LoopHooks(
            on_log=lambda e, g, tot, loss: rank0_print(
                f"step {g}/{tot} loss {loss:.4f}"),
            on_eval=self._eval_and_log,
            save_every=targs.save_steps,
            on_save=self._save_checkpoint,
            # writer drain + rotation are durability work the reported
            # train_runtime must include (files must exist before reload)
            on_end=lambda: (self._drain_writers(), self._rotate()),
            end_save=False,  # best-model adoption below, not Trainer's ritual
        )
        minutes = t.train(self.train_loader, self.dev_loader, hooks=hooks)
        runtime = minutes * 60
        gstep = int(jax.device_get(t.state["step"]))
        if targs.load_best_model_at_end and self.best_ckpt:
            if self._best_params is not None:
                # the exact full-precision params of the best eval step,
                # kept in HBM — bit-equal to reloading a full-precision
                # save of that step, and free of the rotation dtype
                t.state["params"] = self._best_params
                self._best_params = None
                # re-write the best dir at FULL precision (once, outside
                # train_runtime): the on-disk artifact that test_tpu.py
                # sweeps must reproduce the reported best metric exactly,
                # not its bf16-rounded rotation copy
                ckpt.save_params(os.path.join(self.best_ckpt, "model.msgpack"),
                                 {"params": t.state["params"]})
            else:  # defensive: no HBM copy — reload the disk rotation save
                path = os.path.join(self.best_ckpt, "model.msgpack")
                restored = ckpt.load_params(path, t.state["params"])
                # an interrupted run's rotation save may be bf16: restore
                # the live tree's dtypes so the jitted eval signature holds
                t.state["params"] = jax.tree_util.tree_map(
                    lambda r, cur: jnp.asarray(r, getattr(cur, "dtype", None)),
                    restored, t.state["params"])
            rank0_print(f"loaded best model ({targs.metric_for_best_model}="
                        f"{self.best_metric:.4f}) from {self.best_ckpt}")
        # only steps actually executed this run count toward throughput —
        # a resumed run's fast-forwarded steps trained in a previous life
        n_examples = max(0, gstep - start_step) * self.args.train_batch_size
        return {"train_runtime": runtime,
                "train_samples_per_second":
                    n_examples / runtime if runtime > 0 else 0.0,
                "global_step": gstep}

    # ----------------------------------------------------------------- eval
    def evaluate(self) -> Dict[str, float]:
        r = self._trainer.test(self.dev_loader)
        m = self.compute_metrics(np.asarray(r["y_pred"]), np.asarray(r["y_true"]))
        return {"eval_loss": r["loss"], **{f"eval_{k}": v for k, v in m.items()}}

    def _eval_and_log(self, gstep: int) -> None:
        m = self.evaluate()
        rank0_print("  ".join(f"{k} {v:.4f}" for k, v in m.items()))
        key = f"eval_{self.targs.metric_for_best_model}"
        val = m.get(key)
        if val is None:
            return
        better = (self.best_metric is None
                  or (val > self.best_metric) == self.targs.greater_is_better)
        if better:
            self.best_metric = val
            self.best_ckpt = self._ckpt_dir(gstep)
            if self.targs.load_best_model_at_end:
                # full-precision device-held copy (the live buffers are
                # donated): what train() adopts at the end
                self._best_params = jax.tree_util.tree_map(
                    jnp.copy, self._trainer.state["params"])
            # A best model must exist on disk for load_best_model_at_end even
            # when eval_steps is not aligned to save_steps (HF Trainer instead
            # forbids the misalignment); _save_checkpoint dedupes, so a
            # coinciding save_steps boundary won't write twice.
            if self.targs.load_best_model_at_end:
                self._save_checkpoint(gstep)

    # ----------------------------------------------------------- checkpoints
    def _ckpt_dir(self, gstep: int) -> str:
        return os.path.join(self.targs.output_dir, f"checkpoint-{gstep}")

    def _resolve_resume(self, spec: str) -> str:
        """``resume_from_checkpoint``: a checkpoint dir, a train_state file,
        or "latest" (newest rotation dir that has a train_state)."""
        if spec == "latest":
            import glob

            cands = sorted(
                glob.glob(os.path.join(self.targs.output_dir, "checkpoint-*",
                                       "train_state.msgpack")),
                key=lambda p: int(p.split("checkpoint-")[-1].split(os.sep)[0]))
            if not cands:
                raise FileNotFoundError(
                    f"no checkpoint-*/train_state.msgpack under "
                    f"{self.targs.output_dir} — resumable checkpoints need "
                    "save_optimizer_state=True")
            return cands[-1]
        if os.path.isdir(spec):
            spec = os.path.join(spec, "train_state.msgpack")
        if not os.path.exists(spec):
            raise FileNotFoundError(
                f"{spec} not found — resumable checkpoints are written only "
                "under save_optimizer_state=True (params-only rotation saves "
                "cannot restore the optimizer)")
        return spec

    def _restore_trainer_state(self, ckpt_dir: str) -> None:
        """Restore best-model tracking from the checkpoint's
        ``trainer_state.json`` (HF Trainer's file of the same name).  A
        missing file (pre-r5 checkpoint) degrades to fresh tracking — the
        pre-crash best is then only re-discovered if beaten."""
        path = os.path.join(ckpt_dir, "trainer_state.json")
        if not os.path.exists(path):
            return
        with open(path) as f:
            saved = json.load(f)
        best_ckpt = saved.get("best_ckpt")
        if best_ckpt and not os.path.isdir(best_ckpt):
            rank0_print(f"saved best checkpoint {best_ckpt} no longer "
                        "exists; best-model tracking restarts")
            return
        self.best_metric = saved.get("best_metric")
        self.best_ckpt = best_ckpt
        if self.best_metric is not None:
            rank0_print(
                f"restored best {self.targs.metric_for_best_model}="
                f"{self.best_metric:.4f} from {self.best_ckpt} "
                "(rotation will keep protecting it)")

    def _save_checkpoint(self, gstep: int) -> None:
        """Checkpoint WITHOUT stalling the device: snapshot params in HBM
        cast to ``save_dtype`` (the live buffers are donated; the cast also
        halves the bytes when bf16), then fetch + serialize in a writer
        thread that overlaps with continued training.  HF Trainer blocks
        the step loop on every save; at the reference's save_steps=50
        cadence that serialization dominated the epoch before PR 1 (record
        removed, not re-measured on this code), and full-precision fetches
        share the device->host link with the train steps even when
        asynchronous.

        Multi-process runs save synchronously: ``consolidate`` runs
        collective all-gathers, which must not race training collectives on
        another thread."""
        d = self._ckpt_dir(gstep)
        if any(dir_ == d for _, dir_ in self.state_history):
            return  # already written this step (best-model save + save_steps)
        path = os.path.join(d, "model.msgpack")
        if self.targs.save_optimizer_state:
            # the resume artifact (params + moments + step + RNG), written
            # SYNCHRONOUSLY from the live state between steps — full
            # precision by necessity (bitwise resume), which is exactly why
            # it is opt-in: it adds a full-state fetch per save
            ckpt.save_state(os.path.join(d, "train_state.msgpack"),
                            self._trainer.state)
            if jax.process_index() == 0:
                # the trainer_state.json analog: best-model tracking must
                # survive a crash/resume cycle (restored by train())
                with open(os.path.join(d, "trainer_state.json"), "w") as f:
                    json.dump({"best_metric": self.best_metric,
                               "best_ckpt": self.best_ckpt,
                               "global_step": gstep}, f)
        if jax.process_count() > 1:
            ckpt.save_params(path, {
                "params": _cast_like(self._trainer.state["params"],
                                     self.targs.save_dtype)})
        else:
            snap = _cast_like(self._trainer.state["params"],
                              self.targs.save_dtype)

            def write(path=path, snap=snap):
                try:
                    ckpt.save_params(path, {"params": snap})
                except BaseException as e:  # surfaced at the next drain
                    self._writer_errors.append((path, e))

            t = threading.Thread(target=write, daemon=True)
            t.start()
            self._writers.append(t)
        self.state_history.append((gstep, d))
        # bound in-flight disk usage near the user's cap (a few extra dirs
        # may exist transiently while writers overlap training)
        if len(self.state_history) > (self.targs.save_total_limit or 16):
            self._drain_writers()
            self._rotate()

    def _drain_writers(self) -> None:
        for t in self._writers:
            t.join()
        self._writers.clear()
        if self._writer_errors:
            path, err = self._writer_errors[0]
            self._writer_errors.clear()
            raise RuntimeError(
                f"async checkpoint write failed for {path}") from err

    def _rotate(self) -> None:
        if jax.process_index() != 0:
            return
        limit = self.targs.save_total_limit
        while limit and len(self.state_history) > limit:
            _, old = self.state_history.pop(0)
            if old != self.best_ckpt:  # never rotate away the best model
                shutil.rmtree(old, ignore_errors=True)
