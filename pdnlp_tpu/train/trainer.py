"""The Trainer — train / dev / test with the reference's semantics.

Twin of the per-script ``Trainer`` classes
(``/root/reference/multi-gpu-distributed-cls.py:113-239``):

- ``train``: epoch loop, per-step loss line ``【train】 epoch：e/E step：s/S
  loss：x``, optional dev every ``eval_step`` with best-accuracy
  checkpointing (``:183-192``), wall-clock ``耗时：X分钟`` at the end
  (``:193-195``), end-of-run checkpoint when ``dev`` is off (``:196-197``).
- ``dev``: eval over the dev loader -> (mean loss, accuracy) — the psum/
  all-gather math happens inside the jitted eval step.
- ``test``: dev + collected predictions for the classification report.

TPU-specific behavior: the per-step loss is fetched lazily — jax dispatch is
async, so ``float(loss)`` only blocks on steps that actually print
(``log_every``), keeping the device queue full between log lines.  The
reference instead syncs every step (`.item()` after an explicit barrier).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.utils.logging import (
    fmt_best, fmt_dev, fmt_elapsed_minutes, fmt_train, rank0_print,
)
from pdnlp_tpu.utils.profiling import Profiler, StepStats


@dataclasses.dataclass
class LoopHooks:
    """Cadence callbacks for ``Trainer.train`` — ONE epoch/fused-group/
    cadence driver serves both the reference-style Trainer and the managed
    ``AutoTrainer`` (which supplies rotation-checkpoint and best-model
    callbacks here instead of re-implementing the loop; heartbeat, profiler,
    elastic fast-forward, and the fused-boundary guard therefore work
    identically on both paths).

    Every hook receives resolved host values (the loop's async-dispatch
    discipline is preserved around them)."""

    # replaces the 【train】 log line: (epoch, gstep, total_step, loss)
    on_log: Optional[Callable[[int, int, int, float], None]] = None
    # replaces Trainer._dev_and_maybe_save at the eval_step cadence: (gstep)
    on_eval: Optional[Callable[[int], None]] = None
    # extra cadence (e.g. TrainerArgs.save_steps) + its callback: (gstep)
    save_every: Optional[int] = None
    on_save: Optional[Callable[[int], None]] = None
    # runs after the completion barrier but BEFORE the wall-clock stops —
    # work that must count toward the reported runtime (e.g. draining async
    # checkpoint writers so every file is durable)
    on_end: Optional[Callable[[], None]] = None
    # Trainer's native end-of-run ritual (save checkpoint / adopt best);
    # False when the caller owns checkpointing (AutoTrainer)
    end_save: bool = True


class Trainer:
    def __init__(
        self,
        args,
        cfg,
        state: Dict,
        train_step: Callable,
        eval_step: Callable,
        put: Optional[Callable] = None,
        multi_step: Optional[Callable] = None,
        put_fused: Optional[Callable] = None,
        pipeline=None,
        tracer=None,
    ):
        self.args = args
        self.cfg = cfg
        self.state = state
        self.train_step = train_step
        self.eval_step = eval_step
        self.put = put or (lambda b: b)
        # K-step fusion (steps.build_multi_step): one dispatch per K
        # optimizer steps; the loader's remainder runs through train_step
        self.multi_step = multi_step
        self.put_fused = put_fused or self.put
        # input pipeline (data.pipeline): when it wraps the loader train()
        # is given, batches arrive ALREADY on device (resident mode:
        # zero steady-state transport; prefetch: double-buffered upload)
        # and the per-step self.put disappears from the hot loop.  Keyed
        # by loader identity so a trainer handed a different loader falls
        # back to the classic put-in-loop path instead of training on the
        # wrong data.
        self.pipeline = pipeline
        # obs span tracer (pdnlp_tpu.obs): --trace configures the process-
        # global tracer here, so EVERY entrypoint that builds a Trainer
        # gets phase spans + the step breakdown + the regression detector
        # without its own wiring.  Disabled (the default) it is a shared
        # no-op object, not a branch in the hot loop.
        from pdnlp_tpu.obs import trace as _trace

        self.tracer = tracer if tracer is not None \
            else _trace.configure_from_args(args)
        # per-phase mean/p50/p95 of the LAST train() call (None untraced)
        self.trace_summary = None
        self.best_accuracy = 0.0
        self._best_params = None  # device-held copy; written once at end
        # async resume-snapshot writer (train/async_ckpt.py): the in-loop
        # ckpt_save span pays the device->host snapshot only; serialization
        # + crash-atomic publish ride this writer's thread.  Built lazily
        # on the first in-loop save (--ckpt_async, default on); drained
        # before train() reports its runtime.
        self._ckpt_writer = None
        # len(train_loader) of the active train() call — stamped into every
        # resume snapshot's manifest meta so a restart on a DIFFERENT
        # data-parallel width can remap the saved step counter onto its own
        # steps-per-epoch (elastic-width resume)
        self._steps_per_epoch = None
        # manifest meta of the snapshot load_resume restored (None = fresh)
        self._restored_meta = None
        # (minutes-since-train-start, dev accuracy) per in-loop eval: the
        # time-to-accuracy record (minutes to a target accuracy)
        self.eval_history: list = []
        self._t0: Optional[float] = None
        # device-resident eval batches, keyed by loader identity (the held
        # reference keeps the id stable): the dev set is static across the
        # in-loop evals, so re-uploading it every eval only pays transport
        # (~1 MB/batch x 13 batches x 9 evals); HBM cost is the encoded
        # dev set, ~2 MB at 800 x seq 128
        self._eval_cache: Optional[tuple] = None

    def _eval_params(self):
        """Weights eval/checkpointing use: the EMA tree when the state
        carries one (``--ema_decay``), else the live params."""
        return self.state.get("ema", self.state["params"])

    def _use_pipeline(self, loader) -> bool:
        """The pipeline speaks for ``loader`` only when it wraps that exact
        object (identity-keyed, like the eval cache)."""
        return self.pipeline is not None and self.pipeline.loader is loader

    def _routed_attn(self, seq: int, segmented: bool) -> str:
        """The attention impl a train dispatch at this (static) shape routes
        to — ``ops.attention.routed_impl``, the same decision the traced
        step resolves (memoized at the routing point, so the hot loop pays
        a dict hit)."""
        from pdnlp_tpu.ops.attention import routed_impl_cached

        return routed_impl_cached(
            getattr(self.args, "attention_impl", "auto"), seq,
            segmented=segmented,
            dropout=getattr(self.args, "attn_dropout", 0.0) > 0)

    def _first_device_batch(self, train_loader):
        """One device batch shaped/placed exactly like the hot loop's."""
        if self._use_pipeline(train_loader):
            return self.pipeline.warmup_batch(1)
        host = next(iter(train_loader), None)
        return self.put(host) if host is not None else None

    # -------------------------------------------------- warmup / probe
    def warmup_compile(self, train_loader, dev_loader=None) -> None:
        """AOT-compile the step programs before the timed epoch (the
        warm-CUDA-context analog).
        Steps without ``.lower`` (the lazily-built shard_map pipelines)
        compile on their first real call instead — cheap under a warmed
        persistent compile cache.  ``dev_loader`` supplies the eval step's
        real batch shape (dev_batch_size may differ from train's)."""
        use_pipe = self._use_pipeline(train_loader)
        if use_pipe:
            host = None
            batch = self.pipeline.warmup_batch(1)
        else:
            host = next(iter(train_loader), None)
            batch = self.put(host) if host is not None else None
        if batch is None:
            return
        if hasattr(self.train_step, "lower"):
            self.train_step.lower(self.state, batch).compile()
        if self.multi_step is not None and hasattr(self.multi_step, "lower"):
            k = getattr(self.args, "fuse_steps", 1)
            if use_pipe:
                fused = self.pipeline.warmup_batch(k)
                # a short epoch may have no full K-group to warm against
                if fused is not None and fused["input_ids"].ndim == 3:
                    self.multi_step.lower(self.state, fused).compile()
            else:
                stacked = {key: np.stack([v] * k) for key, v in host.items()}
                self.multi_step.lower(self.state,
                                      self.put_fused(stacked)).compile()
        if self.eval_step is not None and hasattr(self.eval_step, "lower"):
            dev_host = (next(iter(dev_loader), None)
                        if dev_loader is not None else None)
            dev_batch = self.put(dev_host) if dev_host is not None else batch
            self.eval_step.lower(self.state["params"], dev_batch).compile()

    def probe_steps_per_sec(self, train_loader, n: int = 30):
        """Steady-state hot-loop rate: ``n`` re-fed steps on a COPY of the
        state (``train_step`` donates its argument), fetched once — the
        controlled per-strategy speed metric, free of loader/eval/transport
        effects.  Returns None when unsupported (host-offloaded moments:
        ``jnp.copy`` would silently move them on-device and probe a
        different program) — and None, not a crash, when the state copy
        itself OOMs: the copy transiently doubles the state's HBM, so a
        near-capacity config that trains fine must still complete its run
        with ``probe n/a`` rather than die inside the probe."""
        if getattr(self.args, "offload_opt_state", False):
            return None
        batch = self._first_device_batch(train_loader)
        if batch is None:
            return None
        import jax.numpy as jnp

        state = m = None
        try:
            state = jax.tree_util.tree_map(jnp.copy, self.state)
            for _ in range(3):
                state, m = self.train_step(state, batch)
            float(jax.device_get(m["loss"]))
            t0 = time.time()
            for _ in range(n):
                state, m = self.train_step(state, batch)
            float(jax.device_get(m["loss"]))
            dt = time.time() - t0
        except jax.errors.JaxRuntimeError as e:  # RESOURCE_EXHAUSTED et al.
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            rank0_print("probe skipped: state copy exceeds device memory")
            return None
        finally:
            del state, m  # release the doubled state promptly
        return n / dt if dt > 0 else None

    def _macro_batches(self, loader, k: int, stage=None):
        """Yield ``(host_batch, n_steps, fused, examples)``: groups of ``k``
        host batches stacked on a leading step axis, remainder as singles.

        Fused groups are assembled into ``stage``'s preallocated ping-pong
        buffers (``data.pipeline._MacroStage``) instead of a fresh
        ``np.stack`` per key per group; the train loop verifies on the
        first fused upload that the uploaded batch does not alias the
        staging memory (identity/zero-copy puts disable reuse) — a yielded
        fused batch is only valid until the next iteration."""
        from pdnlp_tpu.data.pipeline import host_macro_batches

        eff_k = k if self.multi_step is not None else 1
        yield from host_macro_batches(loader, eff_k, stage)

    # ------------------------------------------------------------------ train
    def train(self, train_loader, dev_loader=None,
              hooks: Optional[LoopHooks] = None) -> float:
        """Run ``args.epochs`` epochs; returns wall-clock minutes.

        Elastic hooks (all off by default):  a state restored via
        ``load_resume`` fast-forwards the seeded data order to its step
        counter and continues bitwise; ``args.resume_every`` snapshots full
        state every N steps; ``args.heartbeat_interval`` beats a liveness
        file for the launcher-side ``GangMonitor``.

        ``hooks`` (``LoopHooks``) swaps the log/eval/save behaviors at the
        existing cadences without duplicating the loop — the managed
        ``AutoTrainer`` path runs through here.
        """
        args = self.args
        hooks = hooks or LoopHooks()
        total_step = len(train_loader) * args.epochs
        gstep = 0
        self._steps_per_epoch = len(train_loader)
        # fast-forward: a restored state carries the step it was saved at;
        # the sampler is a seeded permutation, so skipping exactly that many
        # batches replays the identical remaining stream (bitwise resume)
        start_step = int(jax.device_get(self.state["step"]))
        start_step = self._remap_elastic_width(start_step, len(train_loader))
        if start_step > total_step:
            raise ValueError(
                f"restored state is at step {start_step} but this "
                f"configuration trains only {total_step} steps — the "
                "resumed run's epochs/data do not match the saved run's")
        pending: Tuple[int, int, jax.Array] | None = None  # (epoch, gstep, loss)
        last_loss = None
        profiler = Profiler(getattr(args, "profile_dir", None))
        # obs tracing: phase spans feed a per-step breakdown, which feeds
        # the EWMA regression detector (whose smoothed rate rides the
        # heartbeat).  tr is a no-op object when --trace is off — the
        # span/block calls below stay in place unconditionally.
        tr = self.tracer
        breakdown = detector = sampler = None
        if tr.enabled:
            from pdnlp_tpu.obs import (
                MemorySampler, RegressionDetector, StepBreakdown,
            )

            detector = RegressionDetector(
                on_event=lambda ev: rank0_print(f"[obs] {ev}"))
            breakdown = StepBreakdown(on_step=detector.observe)
            tr.add_listener(breakdown.feed)
            # HBM accounting at phase boundaries: the sampler listens for
            # device_block/eval/ckpt_save records and reads the allocator
            # counters (pure host calls — no sync); samples land back in
            # the trace as "hbm" records, so the breakdown table, merged
            # traces and the heartbeat all carry the memory columns.  On
            # backends without memory_stats (CPU) the first sample flips
            # it to a permanent no-op.
            sampler = MemorySampler(tracer=tr)
            tr.add_listener(sampler.feed)
        # live telemetry (--metrics_port / --flight_recorder): Prometheus
        # /metrics + JSON /healthz served off the hot path, plus a bounded
        # flight-recorder JSONL appending snapshots so a SIGKILL'd run
        # still leaves evidence.  Sources snapshot live objects at scrape
        # time; the step loop never sees the exporter.
        exporter = None
        if getattr(args, "metrics_port", 0) \
                or getattr(args, "flight_recorder", None):
            from pdnlp_tpu.obs import memory_snapshot
            from pdnlp_tpu.obs.exporter import build_from_args

            sources = {"memory": (sampler.snapshot if sampler is not None
                                  else memory_snapshot)}
            if breakdown is not None:
                sources["train"] = breakdown.summary
            if self.pipeline is not None \
                    and getattr(self.pipeline, "stats", None) is not None:
                sources["transport"] = self.pipeline.stats.snapshot
            pidx = jax.process_index()
            exporter = build_from_args(
                args, sources, f"flight_proc{pidx}.jsonl",
                process_index=pidx)
            if exporter is not None and exporter.port is not None:
                rank0_print(f"[obs] /metrics + /healthz on "
                            f"http://127.0.0.1:{exporter.port}")
        # the listener must detach even when the loop raises (resume
        # mismatch, fault injection, KeyboardInterrupt): a stale feed
        # on the process-global tracer would double-count every span
        # of the NEXT traced train() in this process
        try:
            fuse = getattr(args, "fuse_steps", 1)
            resume_every = getattr(args, "resume_every", None)
            heartbeat = None
            if getattr(args, "heartbeat_interval", 0) > 0:
                from pdnlp_tpu.parallel.watchdog import Heartbeat

                heartbeat = Heartbeat(args.output_dir, jax.process_index(),
                                      args.heartbeat_interval)
            # chaos hook for the elastic tests: PDNLP_FAULT_STEP kills rank
            # PDNLP_FAULT_PROC at that step — but only on a fresh (non-resumed)
            # incarnation, so the restarted gang survives
            fault_step = int(os.environ.get("PDNLP_FAULT_STEP", "0"))
            fault_proc = int(os.environ.get("PDNLP_FAULT_PROC", "0"))
            examples = 0
            if getattr(args, "warmup_compile", False):
                self.warmup_compile(train_loader, dev_loader)
            if getattr(args, "probe_steps", 0):
                rate = self.probe_steps_per_sec(train_loader, args.probe_steps)
                if rate is not None:
                    rank0_print(f"probe steps/s：{rate:.2f}")
            # the per-step upload route: a pipeline wrapping THIS loader hands
            # over device batches (resident: zero steady-state transport;
            # prefetch: double-buffered upload); otherwise put runs inline (the
            # sync fallback the jaxlint R7 baseline records)
            use_pipe = self._use_pipeline(train_loader)
            stage = None
            if not use_pipe:
                from pdnlp_tpu.data.pipeline import _MacroStage

                stage = _MacroStage(fuse)
            start = time.time()
            self._t0 = start
            for epoch in range(1, args.epochs + 1):
                if gstep + len(train_loader) <= start_step:
                    # resume fast-forward, whole-epoch short-circuit: nothing in
                    # this epoch executes, so don't collate (or, in prefetch
                    # mode, upload) any of its batches — the seeded sampler
                    # makes skipping by count exact
                    gstep += len(train_loader)
                    if heartbeat is not None:
                        heartbeat.beat(step=gstep)
                    continue
                if use_pipe:
                    self.pipeline.set_epoch(epoch - 1)
                    groups = self.pipeline.macro_batches(
                        fuse if self.multi_step is not None else 1)
                else:
                    train_loader.set_epoch(epoch - 1)
                    groups = self._macro_batches(train_loader, fuse, stage)
                # data_wait: host time blocked obtaining each group (collation,
                # the prefetch queue, or the resident gather dispatch)
                groups = tr.wrap_iter("data_wait", groups)
                for batch, n, fused, n_examples in groups:
                    if gstep + n <= start_step:  # already done before the restart
                        gstep += n
                        if heartbeat is not None:  # long fast-forwards stay live
                            heartbeat.beat(step=gstep)
                        continue
                    if gstep < start_step:
                        # the restored step falls inside this fused group:
                        # executing it would re-apply updates the restored
                        # optimizer state already contains
                        raise ValueError(
                            f"resume step {start_step} is not a fused-group "
                            f"boundary under fuse_steps={fuse} (group covers "
                            f"steps {gstep + 1}..{gstep + n}) — resume with the "
                            "fuse_steps the snapshot was saved under, or 1")
                    if fault_step and start_step == 0 and gstep >= fault_step \
                            and jax.process_index() == fault_proc:
                        if os.environ.get("PDNLP_FAULT_KIND") == "sigkill":
                            # the preemption shape: no atexit, no stdio
                            # flush, no collective teardown — peers wedge
                            # in their next collective until the gang
                            # supervisor notices the corpse
                            import signal

                            os.kill(os.getpid(), signal.SIGKILL)
                        os._exit(13)
                    # bucket attr on the dispatch/block spans: the obs
                    # breakdown splits step phases per token width, so a
                    # bucketed run's phase table shows where each bucket's
                    # time goes (int() — shape dims must not leak numpy
                    # scalars into span attrs)
                    seq = int(batch["input_ids"].shape[-1])
                    # the attention impl this dispatch actually routes to
                    # (ops.attention.routed_impl — the same decision the
                    # traced step makes), stamped on the dispatch span so
                    # pallas adoption is visible in trace_tpu.py summarize
                    impl = self._routed_attn(seq, "segment_ids" in batch)
                    if fused:
                        if use_pipe:
                            dev = batch
                        else:
                            with tr.span("h2d_put", step=gstep + n):
                                dev = self.put_fused(batch)
                            if stage is not None:
                                stage.verify(batch, dev)  # aliasing guard, once
                        with tr.span("step_dispatch", step=gstep + n, n=n,
                                     bucket=seq, attn_impl=impl):
                            self.state, metrics = self.multi_step(self.state, dev)
                        last_loss = metrics["loss"][-1]
                    else:
                        if use_pipe:
                            dev = batch
                        else:
                            with tr.span("h2d_put", step=gstep + n):
                                dev = self.put(batch)
                        with tr.span("step_dispatch", step=gstep + n, n=n,
                                     bucket=seq, attn_impl=impl):
                            self.state, metrics = self.train_step(self.state, dev)
                        last_loss = metrics["loss"]
                    # traced runs attribute device time to a separate
                    # device_block span (dispatch above measured enqueue only);
                    # untraced runs keep the async discipline — block is a
                    # no-op on a disabled tracer, never a hidden barrier
                    tr.block(last_loss, step=gstep + n, n=n, bucket=seq)
                    prev = gstep
                    gstep += n
                    examples += n_examples
                    profiler.step(gstep)
                    if heartbeat is not None:
                        heartbeat.beat(
                            step=gstep,
                            steps_per_sec=detector.steps_per_sec
                            if detector is not None else None,
                            **(sampler.beat_payload()
                               if sampler is not None else {}))
                    if resume_every and gstep // resume_every != prev // resume_every:
                        # async (default): the span covers the device->host
                        # snapshot + enqueue only — serialization and disk
                        # ride the writer thread (drained in ckpt_wait)
                        with tr.span("ckpt_save", step=gstep):
                            self._snapshot_resume(args.resume_path())
                    if gstep // args.log_every != prev // args.log_every:
                        if pending is not None:  # print the *previous* line's loss:
                            e, s, l = pending     # it is done by now — no sync stall
                            with tr.span("log", step=gstep):
                                if hooks.on_log is not None:
                                    hooks.on_log(e, s, total_step, float(l))
                                else:
                                    rank0_print(fmt_train(
                                        e, args.epochs, s, total_step, float(l)))
                        pending = (epoch, gstep, last_loss)
                    # boundary-crossing, not equality: with fuse_steps=K the
                    # counter advances K at a time, so when K does not divide
                    # eval_step the eval lands up to K-1 steps late (count per
                    # epoch preserved).  Pick eval_step divisible by fuse_steps
                    # (48 under K=4) for exact reference cadence;
                    # AutoTrainer instead rejects non-divisible combinations.
                    if dev_loader is not None and args.dev and \
                            gstep // args.eval_step != prev // args.eval_step:
                        with tr.span("eval", step=gstep):
                            if hooks.on_eval is not None:
                                hooks.on_eval(gstep)
                            else:
                                self._dev_and_maybe_save(dev_loader)
                    if hooks.save_every and hooks.on_save is not None and \
                            gstep // hooks.save_every != prev // hooks.save_every:
                        hooks.on_save(gstep)
            if pending is not None:
                e, s, l = pending
                if hooks.on_log is not None:
                    hooks.on_log(e, s, total_step, float(l))
                else:
                    rank0_print(fmt_train(e, args.epochs, s, total_step, float(l)))
            # Completion barrier before the clock stops.  Device programs
            # execute in order, so once the last loss and the parameters are
            # ready every prior step has run.  On an attached device
            # block_until_ready is a real barrier by itself (chip_smoke.py's
            # train phase checks it: a fetch after it finds nothing left to
            # wait for); the value fetch is kept because it is the same
            # barrier and surfaces a device-side error here, inside train(),
            # rather than at the caller's first read.
            if last_loss is not None:
                float(jax.device_get(last_loss))
            jax.block_until_ready(self.state["params"])
            # durability drain: every in-flight async snapshot must be
            # published before the run reports its runtime (a preempted
            # host loses unflushed saves; a finished run must not).  Off
            # the step loop by construction — its own ckpt_wait phase, so
            # the in-loop ckpt_save p95 budget stays honest.
            if self._ckpt_writer is not None:
                with tr.span("ckpt_wait", step=gstep):
                    self._ckpt_writer.wait()
            profiler.close()
        finally:
            if breakdown is not None:
                tr.remove_listener(breakdown.feed)
            if sampler is not None:
                tr.remove_listener(sampler.feed)
            if exporter is not None:
                # final flight-recorder snapshot + shutdown on EVERY exit
                # path: a run that raises must still leave its last
                # metrics line on disk
                try:
                    exporter.stop(final_flight=True)
                except Exception:
                    pass
            if self._ckpt_writer is not None:
                # exception path: best-effort drain (bounded) so the newest
                # snapshot survives the failure; errors here must not mask
                # the original exception
                try:
                    self._ckpt_writer.wait(timeout=60.0)
                except Exception:
                    pass
            if breakdown is not None:
                # crash-path flush: the ring + summary land on disk from
                # the finally, so a raising train() (fault injection,
                # preemption, resume mismatch) never silently loses its
                # last steps' spans.  Guarded — telemetry flushing must
                # not mask the original exception — but a flush failure
                # is PRINTED, never swallowed: on a clean run a disk-full
                # OSError here would otherwise surface later as a
                # confusing missing trace_summary.
                try:
                    from pdnlp_tpu.obs import format_table

                    breakdown.close()
                    self.trace_summary = breakdown.summary()
                    path = tr.flush()
                    rank0_print("[obs] phase breakdown:\n"
                                + format_table(self.trace_summary)
                                + (f"\n[obs] spans -> {path}"
                                   if path else ""))
                except Exception as flush_err:  # noqa: BLE001
                    rank0_print(f"WARNING: trace flush failed: "
                                f"{type(flush_err).__name__}: {flush_err}")
        if hooks.on_end is not None:
            hooks.on_end()  # durability work that must count in the runtime
        minutes = (time.time() - start) / 60
        rank0_print(fmt_elapsed_minutes(minutes))
        rank0_print(StepStats(gstep, examples, minutes).line())
        if not hooks.end_save:
            pass  # the caller owns checkpointing (AutoTrainer)
        elif not args.dev:
            self._save(args.ckpt_path())
        elif self._best_params is not None:
            # adopt + persist the best-of-epoch params (the reference's
            # best-checkpoint ritual; its test.py then evaluates that file).
            # Under EMA the snapshot IS averaged weights — both trees adopt
            # it so the post-train test() evaluates exactly what was saved.
            self.state["params"] = self._best_params
            if "ema" in self.state:
                # distinct copy — assigning the same tree would alias the
                # buffers and a further donated train step would invalidate
                # both references
                self.state["ema"] = jax.tree_util.tree_map(
                    jax.numpy.copy, self._best_params)
            ckpt.save_params(args.ckpt_path(), {"params": self._best_params})
        return minutes

    def _dev_and_maybe_save(self, dev_loader) -> None:
        """Eval; keep the best params (the reference checkpoints to disk on
        every improvement INSIDE the timed loop, ``multi-gpu-distributed-
        cls.py:183-192`` — here the best copy stays in HBM and one write
        happens after training, same end state without serializing the epoch
        behind checkpoint I/O)."""
        loss, acc = self.dev(dev_loader)
        rank0_print(fmt_dev(loss, acc))
        if self._t0 is not None:
            # dev() fetched values, so every prior train step has completed:
            # the elapsed time honestly covers the compute that produced acc
            self.eval_history.append(
                {"minutes": (time.time() - self._t0) / 60, "accuracy": acc})
        if acc > self.best_accuracy:
            self.best_accuracy = acc
            # jnp.copy: the live params are donated buffers; the copy is
            # ours.  With EMA enabled the averaged weights ARE the model
            # being evaluated, so they are what "best" snapshots.
            self._best_params = jax.tree_util.tree_map(
                jax.numpy.copy, self._eval_params())
            rank0_print(fmt_best(acc))

    def _save(self, path: str) -> None:
        # all processes enter (consolidate is collective); rank 0 writes
        ckpt.save_params(path, {"params": self._eval_params()})

    # ---------------------------------------------------------------- resume
    def _resume_meta(self) -> Dict:
        """Manifest meta stamped on every resume snapshot: the saved step
        and (when a train() is active) this width's steps-per-epoch — what
        an elastic restart at a DIFFERENT data-parallel width needs to
        remap the data position."""
        meta: Dict = {"step": int(jax.device_get(self.state["step"]))}
        if self._steps_per_epoch:
            meta["steps_per_epoch"] = int(self._steps_per_epoch)
        return meta

    def _resume_writer(self):
        """The lazily built async snapshot writer, or None when the run
        opted back into synchronous saves (``--ckpt_async false``)."""
        if not getattr(self.args, "ckpt_async", True):
            return None
        if self._ckpt_writer is None:
            from pdnlp_tpu.train.async_ckpt import AsyncCheckpointer

            self._ckpt_writer = AsyncCheckpointer()
        return self._ckpt_writer

    def _snapshot_resume(self, path: str) -> None:
        """The in-loop resume snapshot: device→host copy here (inside the
        caller's ``ckpt_save`` span), serialization + crash-atomic publish
        on the async writer's thread — the step loop never blocks on disk,
        and at most one save is in flight (``train/async_ckpt.py``).
        ``--ckpt_async false`` falls back to the synchronous
        :meth:`save_resume`."""
        writer = self._resume_writer()
        if writer is None:
            self.save_resume(path)
            return
        meta = self._resume_meta()
        writer.submit(path, ckpt.snapshot(self.state), meta=meta)
        if self._best_params is not None:
            writer.submit(path + "-best", ckpt.snapshot(self._best_params))
            writer.submit_json(path + "-best.json",
                               {"best_accuracy": self.best_accuracy})

    def save_resume(self, path: str) -> None:
        """Full mid-training snapshot: params + optimizer moments + step +
        RNG, published crash-atomically with a checksum manifest.  The
        reference cannot resume (``SURVEY.md`` §5: no optimizer state
        saving anywhere); this framework can, bitwise.

        The best-of-epoch tracker rides along in sidecar files (``<path>``
        + ``-best``/``-best.json``) so an elastic restart cannot regress the
        shipped best model to a later, worse eval."""
        ckpt.save_state(path, self.state, meta=self._resume_meta())
        if self._best_params is not None:
            ckpt.save_params(path + "-best", {"params": self._best_params})
            if jax.process_index() == 0:
                ckpt.write_json_atomic(path + "-best.json",
                                       {"best_accuracy": self.best_accuracy})

    def load_resume(self, path: str) -> None:
        """Restore a resume snapshot onto the LIVE state's shardings.

        The file always holds fully consolidated host arrays
        (``checkpoint.save`` all-gathers shards before writing), so this is
        consolidate-then-reshard by construction: whatever data-parallel
        width and sharding mode the live state was built with —
        including a width different from the one that saved the snapshot —
        ``_put_like`` re-places every leaf (params AND Adam moments) onto
        the live ``parallel/sharding.py`` specs.  A corrupt file falls back
        to the retained previous snapshot (``checkpoint.read_verified``)
        with a loud warning."""
        raw, meta, used = ckpt.read_verified(path)
        restored = ckpt.from_restored(raw, self.state, path=used)
        self.state = _put_like(restored, self.state)
        self._restored_meta = dict(meta) if meta else {}
        if os.path.exists(path + "-best"):
            # sidecar corruption must not fail the restore: the MAIN state
            # is already valid and adopted — degrade to fresh best-tracking
            # with a loud warning instead of reporting "from scratch"
            try:
                best = ckpt.load_params(path + "-best", self.state["params"])
                import json

                with open(path + "-best.json") as f:
                    acc = json.load(f)["best_accuracy"]
            except (ckpt.CorruptCheckpointError, OSError, ValueError,
                    KeyError):
                rank0_print(f"WARNING: {path}-best sidecar missing/corrupt "
                            "— main state restored; best-accuracy tracking "
                            "restarts from the restored weights")
            else:
                self._best_params = _put_like(best, self.state["params"])
                self.best_accuracy = acc

    def _remap_elastic_width(self, start_step: int, spe: int) -> int:
        """Map a restored step counter onto THIS run's steps-per-epoch.

        Same width (or fresh start): identity — resume stays bitwise.  A
        snapshot saved under a different data-parallel width carries its
        ``steps_per_epoch`` in the manifest meta; the data position then
        continues by EPOCH FRACTION (ceil: examples the old optimizer
        already consumed are never re-applied; at most one new-width
        batch's worth of rows is skipped instead).  The on-device step
        counter is rebased to the remapped value so subsequent snapshots,
        fast-forward math, and log lines all speak this width's units.
        Optimizer state (Adam moments + count) is restored exactly —
        elastic resume changes the data layout, never the training math
        already done."""
        meta, self._restored_meta = (self._restored_meta or {}), None
        old_spe = meta.get("steps_per_epoch")
        if not start_step or not old_spe or old_spe == spe:
            return start_step
        remapped = -(-start_step * spe // old_spe)  # ceil
        fuse = getattr(self.args, "fuse_steps", 1)
        if self.multi_step is not None and fuse > 1:
            # resume must land on a fused-group boundary (train() rejects
            # interior steps); round up — same skip-don't-replay policy
            remapped = -(-remapped // fuse) * fuse
        rank0_print(
            f"elastic resume: remapped step {start_step} (of {old_spe}/epoch "
            f"at save time) -> {remapped} (of {spe}/epoch at this width); "
            "data position continues by epoch fraction, optimizer state is "
            "exact")
        like = self.state["step"]
        self.state["step"] = _put_like(
            np.asarray(remapped, dtype=getattr(like, "dtype", np.int32)), like)
        return remapped

    # ------------------------------------------------------------------- eval
    def _evaluate(self, loader, collect_preds: bool,
                  static_eval: bool = True) -> Dict:
        # Dispatch the whole pass first, fetch once at the end: a per-batch
        # float() would serialize host and device through the dev set (the
        # train loop's async-dispatch treatment, applied to eval).
        if not static_eval:
            # shuffling/augmenting loader: re-upload THIS iteration's
            # batches and leave the identity-keyed cache untouched (a
            # static loader used elsewhere keeps its device copy)
            batches = [self.put(b) for b in loader]
        else:
            if self._eval_cache is None or self._eval_cache[0] is not loader:
                self._eval_cache = (loader, [self.put(b) for b in loader])
            batches = self._eval_cache[1]
        pending = [self.eval_step(self._eval_params(), batch)
                   for batch in batches]
        fetched = jax.device_get(pending)
        y_true, y_pred = [], []
        loss_sum = weight = correct = 0.0
        for m in fetched:
            loss_sum += float(m["loss_sum"])
            weight += float(m["weight"])
            correct += float(m["correct"])
            if collect_preds:
                real = np.asarray(m["ew"]) > 0  # drop filler rows
                y_pred.extend(np.asarray(m["pred"])[real].tolist())
                y_true.extend(np.asarray(m["label"])[real].tolist())
        weight = max(weight, 1.0)
        return {"loss": loss_sum / weight, "accuracy": correct / weight,
                "y_true": y_true, "y_pred": y_pred}

    def dev(self, loader, static_eval: bool = True) -> Tuple[float, float]:
        """(weighted mean loss, accuracy) over the dev set.

        ``static_eval=True`` (default) caches the eval batches on device
        keyed by loader IDENTITY (``_evaluate``), so the loader must yield
        the same batches on every iteration — the shipped ``shuffle=False``
        dev loaders satisfy this, and the in-loop eval cadence then pays
        upload transport once instead of per eval.  A shuffling or
        augmenting loader would be silently evaluated on its FIRST
        iteration's frozen batches forever: pass ``static_eval=False`` for
        such loaders to re-upload fresh batches on every call (the cache,
        if any, is left untouched).
        """
        r = self._evaluate(loader, collect_preds=False,
                           static_eval=static_eval)
        return r["loss"], r["accuracy"]

    def test(self, loader, static_eval: bool = True) -> Dict:
        """Eval + predictions: feeds the classification report
        (``/root/reference/test.py:144-170``).

        Shares ``dev()``'s device-side batch cache and therefore its
        static-content requirement: the loader must yield identical batches
        on every iteration, unless ``static_eval=False`` (see :meth:`dev`).
        """
        return self._evaluate(loader, collect_preds=True,
                              static_eval=static_eval)


def _shardings_of(state):
    """Current sharding tree of a live state (resume re-places restored host
    arrays exactly where the originals lived — replicated or ZeRO-sharded)."""
    return jax.tree_util.tree_map(
        lambda x: x.sharding if isinstance(x, jax.Array) else None, state)


def _put_like(host_tree, live_tree):
    """Place a restored host tree onto the live tree's shardings.

    Single-process shardings are fully addressable and go through
    ``device_put``.  Multi-process shardings span other hosts' devices, which
    plain ``device_put`` refuses — every process read the same snapshot, so
    each materializes its own addressable shards of the global array
    (``make_array_from_callback`` slices the host copy per shard)."""
    shardings = _shardings_of(live_tree)
    if all(getattr(s, "is_fully_addressable", True)
           for s in jax.tree_util.tree_leaves(shardings)):
        return jax.device_put(host_tree, shardings)

    def put(x, sh):
        if jax.dtypes.issubdtype(getattr(x, "dtype", np.float32),
                                 jax.dtypes.prng_key):
            data = np.asarray(jax.random.key_data(x))
            g = jax.make_array_from_callback(
                data.shape, sh, lambda idx: data[idx])
            return jax.random.wrap_key_data(g, impl=jax.random.key_impl(x))
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx])

    return jax.tree_util.tree_map(put, host_tree, shardings)
