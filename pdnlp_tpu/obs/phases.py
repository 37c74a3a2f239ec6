"""The canonical per-step phase taxonomy + the per-step aggregator.

Every traced layer names its spans out of ONE vocabulary, so a trace from
the trainer, the input pipeline, and the checkpoint writer composes into a
single per-step breakdown — and ``trace_tpu.py diff`` can compare any two
runs phase by phase:

====================  =====================================================
phase                 host-side meaning
====================  =====================================================
``data_wait``         blocked obtaining the next batch (collation, the
                      prefetch queue, the resident gather dispatch)
``h2d_put``           blocked inside a host->device upload (``put``); the
                      resident pipeline's amortized uploads carry
                      ``in_loop=False``
``step_dispatch``     enqueueing the jitted train step (async: this is
                      dispatch latency, NOT compute)
``device_block``      ``block_until_ready`` on the step's output — where
                      device compute time actually surfaces on the host
``eval``              the in-loop dev pass
``ckpt_save``         the step loop's checkpoint pause — under the async
                      writer (``--ckpt_async``, default) this is the
                      device→host snapshot + enqueue ONLY (serialization
                      and disk ride the writer thread); under
                      ``--ckpt_async false`` it is the full synchronous
                      save.  ``trace_tpu.py diff --ckpt_save_budget``
                      gates its p95
``ckpt_wait``         end-of-run drain of the async checkpoint writer —
                      durability work off the step loop, counted in the
                      runtime but never in ``ckpt_save``'s in-loop p95
``log``               formatting + printing the loss line
====================  =====================================================

:class:`StepBreakdown` folds a span stream into per-step phase totals and
summarizes mean/p50/p95 per phase.  It is a tracer *listener* (feed it via
``tracer.add_listener(breakdown.feed)``): a ``device_block`` span closes
the current step — the traced loop emits exactly one per optimizer-step
group — so fused K-step dispatches aggregate correctly through the
record's ``n`` attribute.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence

from pdnlp_tpu.obs.trace import ROUND_LEAVES, ROUND_RECORD

PHASES = ("data_wait", "h2d_put", "step_dispatch", "device_block",
          "eval", "ckpt_save", "ckpt_wait", "log")

#: the phase that marks "this optimizer-step group is finished" in a span
#: stream (the traced loop's per-step barrier)
STEP_END_PHASE = "device_block"

#: span attrs tallied as adoption counters (any span name, incl. the serve
#: vocabulary): ``attn_impl`` = the routed attention kernel on a dispatch,
#: ``dtype`` = the serve forward precision (``"int8"`` under weight-
#: quantized serving)
_ADOPTION_ATTRS = ("attn_impl", "dtype")

#: the serve-side span vocabulary: ``queue_wait`` (batcher/router pre-batch
#: wait, ``retry`` attr counts re-dispatched requests), ``forward`` /
#: ``compile`` (engine execution, cache hit vs first-seen shape; packed
#: forwards additionally carry ``packed``/``fill``/``segments`` attrs —
#: token-level fill and riding-request count per batch), ``swap`` (a
#: rolling checkpoint hot-swap).  Generative decoding adds ``prefill``
#: (bucketed causal prompt forward + KV insert, ``streams``/``tokens``
#: attrs), ``decode`` (ONE fixed-shape step over the slot block,
#: ``live`` attr = rows actually advancing) and ``verify`` (a speculative
#: round's scoring call) — derived from the engine call's leaves, see
#: ``CALL_LEAVES`` below.  Spans carrying a ``replica``
#: attr feed the PER-REPLICA phase tables — one sick replica must show up
#: as itself in ``trace_tpu.py summarize``, not as a pool-average smear.
SERVE_PHASES = ("queue_wait", "forward", "compile", "swap", "prefill",
                "decode", "verify")

#: the decode worker's LEAF vocabulary (``Tracer.leaf`` — on the ring
#: buffer and, as ``bench:<name>``, on the JAX profiler's host plane).  One
#: round of ``serve.decode.DecodeBatcher`` is, never nested:
#:
#: - ``admit`` — the locked seating section, only when it seats: expiry,
#:   prefix lookup, page reservation (``seated``, ``waiting``);
#: - per engine call ``<p>`` in ``decode`` / ``chunk`` / ``prefill`` /
#:   ``cow`` / ``verify``: ``<p>.dispatch`` (host arrays built, the jitted
#:   call(s) enqueued; carries ``phase`` — the SERVE_PHASES name of the
#:   call, ``compile`` on a first-seen shape — the shape attrs and the
#:   bounded ``request_ids`` exemplars), ``<p>.device_wait``
#:   (``block_until_ready`` on what the host reads back, nothing else) and
#:   ``<p>.fetch`` (``device_get`` to numpy; ``bytes``: the ids the launch
#:   chose, 4 a row — the logits only of a verify window); a COW flush is
#:   its dispatch alone;
#: - ``<p>.emit`` — the batcher's work on the fetched ids: the block's
#:   advance in one pass, push, detach, gauges (``rows``).
#:
#: Every leaf carries ``replica`` and ``round`` (the worker's round
#: counter: the span that caused it).  The ``prefill`` / ``decode`` /
#: ``compile`` / ``verify`` phase of the per-replica tables is DERIVED:
#: one observation per engine call, from its dispatch's start to its
#: fetch's end — the call is not timed a second time.
CALL_LEAVES = ("dispatch", "device_wait", "fetch")
EMIT_LEAF = "emit"
ADMIT_LEAF = "admit"


def worker_leaf(name: Optional[str]) -> Optional[str]:
    """``"fetch"`` for ``decode.fetch``, ``"admit"`` for the seating leaf,
    ``None`` for a record that is no leaf of the decode worker's round."""
    if name == ADMIT_LEAF:
        return ADMIT_LEAF
    call, _, leaf = (name or "").rpartition(".")
    return leaf if call and leaf in CALL_LEAVES + (EMIT_LEAF,) else None


def _bucket_key(bucket) -> tuple:
    """Numeric-aware sort for bucket labels: widths 16/32/64/128 order by
    VALUE (a plain string sort reads 128 < 16), non-numeric labels after."""
    try:
        return (0, int(bucket), "")
    except (TypeError, ValueError):
        return (1, 0, str(bucket))


def _percentile(sorted_vals: Sequence[float], p: float) -> float:
    """Exact percentile over a sorted list (numpy-free: the CLI must run
    without the training stack)."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    k = (len(sorted_vals) - 1) * (p / 100.0)
    lo = int(k)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = k - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


class StepBreakdown:
    """Per-step phase accumulator -> per-phase mean/p50/p95.

    ``feed(record)`` accepts tracer span records; per-STEP totals (a step
    may contain several spans of one phase) are closed by the
    ``device_block`` record and become one observation per phase.  Spans
    whose name is not a known phase are ignored — serve traces flow through
    the same tracer with their own vocabulary.  Phase seconds are SELF
    time: a phase span nested inside another phase span (same thread,
    contained interval) has its duration subtracted from the enclosing
    one, so sync mode's in-``next`` upload counts as ``h2d_put``, not as
    ``h2d_put`` + ``data_wait`` twice.  ``feed`` is thread-safe — the
    prefetch worker's spans arrive on its own thread.

    ``on_step(step, phases, wall)`` fires as each step closes — the
    regression detector's input — with ``step`` the global step counter
    (from the ``device_block`` record's ``step`` attr when present, else a
    running count), ``phases`` the step's phase->seconds dict, and ``wall``
    the step's total traced seconds.
    """

    def __init__(self, on_step: Optional[Callable[[int, Dict[str, float],
                                                   float], None]] = None):
        self.on_step = on_step
        self.steps = 0            # optimizer steps (fused groups count n)
        self.groups = 0           # dispatch groups (= observations)
        self._current: Dict[str, float] = {}
        self._per_phase: Dict[str, List[float]] = {}
        # per-bucket (the closing record's ``bucket`` attr, e.g. the batch
        # token width under --length_mode bucket) phase totals: the
        # end-of-train table breaks the step phases down per bucket
        self._per_bucket: Dict[object, Dict] = {}
        self._count = 0
        # feed() runs on whichever thread RECORDED the span (tracer
        # listeners fire in-line) — the prefetch worker's h2d_put races the
        # main thread's step spans without this
        self._lock = threading.Lock()
        self._children: Dict[int, List] = {}  # tid -> [(t0, t1, dur, depth)]
        # kernel/precision adoption counters: spans carrying an
        # ``attn_impl`` (train dispatch) or ``dtype`` (serve forward) attr
        # are tallied by value, so ``summarize``/the end-of-train table
        # show WHICH impl the hot path actually ran, not just how long
        self._impls: Dict[str, Dict[str, int]] = {}
        # per-replica serve-phase durations (SERVE_PHASES spans with a
        # ``replica`` attr) + retry counts from queue_wait records
        self._serve: Dict[object, Dict[str, List[float]]] = {}
        self._serve_retries: Dict[object, int] = {}
        # per-replica token-level fill of executed forwards (the ``fill``
        # attr engine spans carry) + how many of them were packed batches
        self._serve_fill: Dict[object, List[float]] = {}
        self._serve_packed: Dict[object, int] = {}
        # device-memory accounting: "hbm" records (obs.memory samplers) and
        # per-forward ``hbm_peak`` span attrs feed the memory columns — the
        # peak is the HBM-budget number, last is the live occupancy
        self._hbm_peak = 0
        self._hbm_last = 0
        self._serve_hbm: Dict[object, int] = {}   # replica -> peak bytes
        # per-rank sub-summaries of a merged multi-process trace
        # (from_records splits by pid so rank A's device_block can never
        # close a step holding rank B's phases)
        self._by_rank: Dict[int, Dict] = {}
        # tid -> (t0, phase, attrs) of the engine call whose dispatch
        # leaf was fed and whose fetch leaf has not been yet
        self._open_calls: Dict[int, tuple] = {}

    # ------------------------------------------------------------- feeding
    def feed(self, record: Dict) -> None:
        name = record.get("name")
        attrs = record.get("attrs") or {}
        if name == "hbm":  # memory sample (obs.memory.MemorySampler)
            with self._lock:
                self._hbm_last = int(attrs.get("bytes_in_use", 0))
                self._hbm_peak = max(self._hbm_peak,
                                     int(attrs.get("peak_bytes", 0)))
            return
        for key in _ADOPTION_ATTRS:
            v = attrs.get(key)
            if v is not None:
                with self._lock:
                    by = self._impls.setdefault(key, {})
                    by[str(v)] = by.get(str(v), 0) + 1
        leaf = worker_leaf(name)
        if leaf in CALL_LEAVES:
            # an engine call's parent record, derived from its leaves
            tid = record.get("tid", 0)
            with self._lock:
                if leaf == "dispatch" and attrs.get("phase"):
                    self._open_calls[tid] = (
                        float(record.get("t0", 0.0)), attrs["phase"], attrs)
                opened = self._open_calls.pop(tid, None) \
                    if leaf == "fetch" else None
            if opened is None:
                return
            t0, name, attrs = opened
            record = {"dur": float(record.get("t0", 0.0))
                      + float(record.get("dur", 0.0)) - t0}
        if name in SERVE_PHASES and "replica" in attrs:
            with self._lock:
                per = self._serve.setdefault(attrs["replica"], {})
                per.setdefault(name, []).append(
                    float(record.get("dur", 0.0)))
                retry = attrs.get("retry")
                if retry:
                    self._serve_retries[attrs["replica"]] = \
                        self._serve_retries.get(attrs["replica"], 0) \
                        + int(retry)
                # fill aggregates FORWARD spans only: every compile span
                # is a warmup dummy ([[CLS],[SEP]] at ~0.002 fill) and
                # would drag a healthy replica's reported fill far below
                # its steady state (the router snapshot's fill_ratio
                # already excludes warmups — the two surfaces must agree)
                if name == "forward" and attrs.get("fill") is not None:
                    self._serve_fill.setdefault(
                        attrs["replica"], []).append(float(attrs["fill"]))
                    if attrs.get("packed"):
                        self._serve_packed[attrs["replica"]] = \
                            self._serve_packed.get(attrs["replica"], 0) + 1
                if attrs.get("hbm_peak") is not None:
                    # peak HBM per replica: the engine samples its mesh
                    # slice's allocator before each executed batch
                    self._serve_hbm[attrs["replica"]] = max(
                        self._serve_hbm.get(attrs["replica"], 0),
                        int(attrs["hbm_peak"]))
        if name not in PHASES:
            return
        full = float(record.get("dur", 0.0))
        dur = full
        depth = int(record.get("depth", 0))
        tid = record.get("tid", 0)
        t0 = float(record.get("t0", 0.0))
        t1 = t0 + full
        with self._lock:
            # SELF time, not inclusive time: a phase span can lexically
            # contain another phase span on its thread (sync mode's
            # h2d_put runs inside the data_wait span around ``next``), and
            # spans complete child-first — so subtract already-fed DEEPER
            # spans this one contains, and each second lands in exactly
            # one phase instead of being double-counted.
            pending = self._children.get(tid)
            if pending:
                kept = []
                for c in pending:
                    if c[3] > depth and c[0] >= t0 and c[1] <= t1:
                        dur -= c[2]
                    else:
                        kept.append(c)
                self._children[tid] = kept
            if depth > 0:  # only nested spans can be someone's child
                # the FULL duration: a grandparent subtracts the whole
                # consumed subtree exactly once
                self._children.setdefault(tid, []).append(
                    (t0, t1, full, depth))
                del self._children[tid][:-64]  # bound orphaned children
            self._current[name] = self._current.get(name, 0.0) \
                + max(0.0, dur)
            if name == STEP_END_PHASE:
                attrs = record.get("attrs") or {}
                self._close_step(attrs.get("step"), int(attrs.get("n", 1)),
                                 bucket=attrs.get("bucket"))

    def record(self, phase: str, seconds: float) -> None:
        """Direct accumulation into the open step (tests / non-span use)."""
        with self._lock:
            self._current[phase] = self._current.get(phase, 0.0) \
                + float(seconds)

    def end_step(self, step: Optional[int] = None, n: int = 1) -> None:
        """Close the open step explicitly (loops without a block span)."""
        with self._lock:
            self._close_step(step, n)

    def _close_step(self, step: Optional[int], n: int,
                    bucket=None) -> None:
        # caller holds self._lock
        phases = self._current
        self._current = {}
        if n > 0:  # n=0 marks a trailing partial flush, not a real step
            self.groups += 1
            self.steps += int(n)
        self._count = int(step) if step is not None else self._count + n
        for phase, sec in phases.items():
            self._per_phase.setdefault(phase, []).append(sec)
        if bucket is not None and n > 0:
            b = self._per_bucket.setdefault(
                bucket, {"steps": 0, "groups": 0, "phases": {}})
            b["steps"] += int(n)
            b["groups"] += 1
            for phase, sec in phases.items():
                b["phases"][phase] = b["phases"].get(phase, 0.0) + sec
        if self.on_step is not None:
            self.on_step(self._count, phases, sum(phases.values()))

    def close(self) -> None:
        """Flush a trailing partial step (spans after the last barrier)."""
        with self._lock:
            if self._current:
                self._close_step(None, 0)

    # ------------------------------------------------------------- summary
    def summary(self) -> Dict:
        """JSON-ready per-phase stats: seconds mean/p50/p95/total/count,
        plus share of the traced wall time.  Takes the feed lock: the
        live exporter snapshots a RUNNING breakdown from its own thread,
        and iterating ``_per_phase`` while a first-seen phase key lands
        would raise mid-scrape."""
        with self._lock:
            return self._summary_locked()

    def _summary_locked(self) -> Dict:
        phases = {}
        grand = sum(sum(v) for v in self._per_phase.values()) or 1.0
        for phase, vals in sorted(self._per_phase.items(),
                                  key=lambda kv: -sum(kv[1])):
            s = sorted(vals)
            total = sum(vals)
            phases[phase] = {
                "count": len(vals),
                "total_sec": round(total, 6),
                "mean_sec": round(total / len(vals), 9),
                "p50_sec": round(_percentile(s, 50), 9),
                "p95_sec": round(_percentile(s, 95), 9),
                "share": round(total / grand, 4),
            }
        out = {"steps": self.steps, "groups": self.groups, "phases": phases}
        if self._impls:
            out["impls"] = {k: dict(sorted(v.items(), key=lambda kv: -kv[1]))
                            for k, v in sorted(self._impls.items())}
        if self._serve:
            out["serve_by_replica"] = {
                str(rep): {
                    "retries": self._serve_retries.get(rep, 0),
                    # token-level fill of this replica's executed forwards
                    # (None when its spans predate the fill attr)
                    "fill_mean": (round(sum(self._serve_fill[rep])
                                        / len(self._serve_fill[rep]), 4)
                                  if self._serve_fill.get(rep) else None),
                    "packed_batches": self._serve_packed.get(rep, 0),
                    # peak HBM of this replica's device slice (None on
                    # backends without memory_stats, e.g. CPU)
                    "hbm_peak_gb": (round(
                        self._serve_hbm[rep] / 2**30, 3)
                        if rep in self._serve_hbm else None),
                    "phases": {
                        phase: {
                            "count": len(vals),
                            "total_sec": round(sum(vals), 6),
                            "mean_sec": round(sum(vals) / len(vals), 9),
                            "p95_sec": round(
                                _percentile(sorted(vals), 95), 9),
                        }
                        for phase, vals in sorted(
                            per.items(), key=lambda kv: -sum(kv[1]))
                    },
                }
                for rep, per in sorted(self._serve.items(),
                                       key=lambda kv: _bucket_key(kv[0]))
            }
        if self._per_bucket:
            out["by_bucket"] = {
                str(bucket): {
                    "steps": b["steps"],
                    "groups": b["groups"],
                    "phases": {
                        phase: {
                            "total_sec": round(sec, 6),
                            "mean_sec": round(sec / b["groups"], 9),
                        }
                        for phase, sec in sorted(b["phases"].items(),
                                                 key=lambda kv: -kv[1])
                    },
                }
                for bucket, b in sorted(self._per_bucket.items(),
                                        key=lambda kv: _bucket_key(kv[0]))
            }
        if self._hbm_peak:
            out["memory"] = {
                "peak_bytes": self._hbm_peak,
                "bytes_in_use": self._hbm_last,
                "gb_peak": round(self._hbm_peak / 2**30, 3),
            }
        if self._by_rank:
            out["by_rank"] = {str(rank): s for rank, s
                              in sorted(self._by_rank.items())}
        return out

    @staticmethod
    def from_records(records: Sequence[Dict]) -> "StepBreakdown":
        """Rebuild a breakdown from an exported span stream (the CLI's
        ``summarize``/``diff`` path).

        A MERGED multi-rank trace (``trace_tpu.py merge``) interleaves
        processes; folding it through one accumulator would let rank A's
        ``device_block`` close a step holding rank B's phases.  Records
        are therefore split by ``pid`` and folded per rank; the returned
        breakdown aggregates the per-rank observations (every step of
        every rank is one observation) and keeps each rank's own summary
        under ``summary()["by_rank"]``."""
        by_pid: Dict[int, List[Dict]] = {}
        for rec in records:
            by_pid.setdefault(int(rec.get("pid", 0)), []).append(rec)
        if len(by_pid) <= 1:
            bd = StepBreakdown()
            for rec in records:
                bd.feed(rec)
            bd.close()
            return bd
        merged = StepBreakdown()
        for pid in sorted(by_pid):
            merged._absorb(StepBreakdown.from_records(by_pid[pid]), pid)
        return merged

    def _absorb(self, other: "StepBreakdown", rank: int) -> None:
        """Fold one rank's closed breakdown into this multi-rank one."""
        with self._lock:
            self.steps += other.steps
            self.groups += other.groups
            self._count += other._count
            for phase, vals in other._per_phase.items():
                self._per_phase.setdefault(phase, []).extend(vals)
            for key, by in other._impls.items():
                mine = self._impls.setdefault(key, {})
                for val, n in by.items():
                    mine[val] = mine.get(val, 0) + n
            for rep, per in other._serve.items():
                mine = self._serve.setdefault(rep, {})
                for phase, vals in per.items():
                    mine.setdefault(phase, []).extend(vals)
            for rep, n in other._serve_retries.items():
                self._serve_retries[rep] = \
                    self._serve_retries.get(rep, 0) + n
            for rep, vals in other._serve_fill.items():
                self._serve_fill.setdefault(rep, []).extend(vals)
            for rep, n in other._serve_packed.items():
                self._serve_packed[rep] = \
                    self._serve_packed.get(rep, 0) + n
            for rep, peak in other._serve_hbm.items():
                self._serve_hbm[rep] = max(
                    self._serve_hbm.get(rep, 0), peak)
            for bucket, b in other._per_bucket.items():
                mine = self._per_bucket.setdefault(
                    bucket, {"steps": 0, "groups": 0, "phases": {}})
                mine["steps"] += b["steps"]
                mine["groups"] += b["groups"]
                for phase, sec in b["phases"].items():
                    mine["phases"][phase] = \
                        mine["phases"].get(phase, 0.0) + sec
            self._hbm_peak = max(self._hbm_peak, other._hbm_peak)
            self._hbm_last = max(self._hbm_last, other._hbm_last)
            self._by_rank[rank] = other.summary()


def format_table(summary: Dict) -> str:
    """The phase table: one aligned text block (``trace_tpu.py summarize``
    and the end-of-train print share it)."""
    header = (f"{'phase':<14} {'count':>7} {'total_s':>10} {'mean_ms':>10} "
              f"{'p50_ms':>10} {'p95_ms':>10} {'share':>7}")
    lines = [header, "-" * len(header)]
    for phase, s in summary.get("phases", {}).items():
        lines.append(
            f"{phase:<14} {s['count']:>7d} {s['total_sec']:>10.3f} "
            f"{s['mean_sec'] * 1e3:>10.3f} {s['p50_sec'] * 1e3:>10.3f} "
            f"{s['p95_sec'] * 1e3:>10.3f} {s['share']:>6.1%}")
    lines.append(f"steps: {summary.get('steps', 0)}  "
                 f"dispatch groups: {summary.get('groups', 0)}")
    # memory line (obs.memory samples): the HBM-budget number next to the
    # time budget — absent on backends without memory_stats (CPU)
    mem = summary.get("memory")
    if mem:
        lines.append(f"peak HBM {mem['gb_peak']:.3f} GB "
                     f"(in use {mem['bytes_in_use'] / 2**30:.3f} GB)")
    # adoption line (kernel/precision): which impl the hot path actually
    # ran — `attn_impl: pallas x384` is the pallas-is-default receipt
    for key, by in summary.get("impls", {}).items():
        lines.append(f"{key}: " + "  ".join(
            f"{val} x{n}" for val, n in by.items()))
    # per-replica serve tables (router runs): one block per replica so a
    # slow or retry-heavy replica reads as ITSELF, not a pool average
    for rep, b in summary.get("serve_by_replica", {}).items():
        line = f"replica {rep}: {b['retries']} retried request(s)"
        if b.get("fill_mean") is not None:
            line += (f"  fill {b['fill_mean']:.2f}"
                     f" ({b.get('packed_batches', 0)} packed batch(es))")
        if b.get("hbm_peak_gb") is not None:
            line += f"  peak HBM {b['hbm_peak_gb']:.3f} GB"
        lines.append(line)
        for phase, s in b["phases"].items():
            lines.append(
                f"  {phase:<12} {s['count']:>6d}x {s['total_sec']:>10.3f}s "
                f"total {s['mean_sec'] * 1e3:>10.3f} ms mean "
                f"{s['p95_sec'] * 1e3:>10.3f} ms p95")
    # per-rank lines (merged multi-rank traces): each rank's step count,
    # wall share, and peak HBM — a stalled or memory-pressured rank reads
    # as ITSELF, not as a gang-average smear
    for rank, s in summary.get("by_rank", {}).items():
        total = sum(p["total_sec"] for p in s.get("phases", {}).values())
        line = (f"rank {rank}: {s.get('steps', 0)} steps / "
                f"{s.get('groups', 0)} groups  {total:.3f}s traced")
        rmem = s.get("memory")
        if rmem:
            line += f"  peak HBM {rmem['gb_peak']:.3f} GB"
        lines.append(line)
    # per-bucket breakdown (length-aware runs): one line per bucket x
    # phase so a bucketed run's table shows where each width's time goes
    for bucket, b in summary.get("by_bucket", {}).items():
        lines.append(f"bucket {bucket}: {b['steps']} steps / "
                     f"{b['groups']} groups")
        for phase, s in b["phases"].items():
            lines.append(
                f"  {phase:<12} {s['total_sec']:>10.3f}s total "
                f"{s['mean_sec'] * 1e3:>10.3f} ms/group")
    return "\n".join(lines)


# ------------------------------------------------ decode worker host phases

def decode_host_phases(records: Sequence[Dict]) -> Dict:
    """Where a decode round's HOST time goes, from the worker's leaf spans
    (``CALL_LEAVES``/``EMIT_LEAF``/``ADMIT_LEAF``) of one span stream; per
    replica.  ``steps`` = the count of ``decode.dispatch`` leaves; each
    leaf: ``count``, ``ms_per_step`` (its total over ``steps``), ``mean_ms``
    and ``p95_ms`` of one span.  ``host_exposed_share`` = the worker's wall
    time from its first leaf to its last, less every ``*.device_wait``,
    over that wall time: the share of a round in which the device can
    only wait for the host.  Calls that compiled (``phase="compile"``:
    warm-up) and a drafter engine's calls are left out.
    ``kv_read_amplification`` = cached positions the decode steps'
    attention covered (``kv_positions_read`` of ``decode.dispatch``: rows x
    extent) over the positions that were live (``kv_positions_live``); per
    decode step it is the same ratio, both sums having ``steps`` terms.
    ``chunk_kv_read_amplification`` the same over ``chunk.dispatch``.
    ``decode_row_rungs`` = the share of decode steps launched at each row
    extent (``rows`` of ``decode.dispatch``: the engine's row rung, chosen
    by the highest attached slot), keyed by the extent.
    ``decode_attend`` = the share of decode steps whose attention walked
    each row's live pages (``"kernel"``, ``ops/paged.py``) or gathered the
    page rung (``"gather"``), from ``decode.dispatch``'s ``attend``.
    ``expert_load`` (a family with sparse experts; from ``decode.fetch``'s
    ``expert_assignments`` / ``expert_rows_computed`` / ``expert_tokens_max``
    / ``experts_idle``): assignments to the held experts a decode step, the
    busiest held expert's tokens a step, idle held experts a step,
    ``expert_fill`` = assignments over the rows the experts' products
    computed for them (whole tiles: how far the row tile follows the load),
    and ``cache_bytes_per_token`` as ``decode.dispatch`` states it.
    ``decode_fetch_bytes_per_step`` = the ``bytes`` of the ``decode.fetch``
    leaves over their count: what a decode launch hands the host (the
    chosen ids, 4 bytes a slot, plus a family's expert counts).
    ``state_bytes_per_step`` (a family with recurrent layers; from
    ``decode.dispatch``'s ``state_bytes``): the per-slot state a decode step
    reads and writes, and ``state_bytes_share`` = its share of what the step
    moves of per-STREAM memory (it and the cached positions the step's
    attention covers, ``kv_positions_read x cache_bytes_per_token``).
    Empty when the stream holds no leaf."""
    per: Dict[object, Dict[str, List[float]]] = {}
    kv: Dict[object, Dict[str, List[int]]] = {}    # rep -> call -> [read, live]
    # rep -> [launches, assignments, busiest, idle, rows computed]
    experts: Dict[object, List[int]] = {}
    fetched: Dict[object, List[int]] = {}   # rep -> [decode fetches, bytes]
    rungs: Dict[object, Dict[int, int]] = {}   # rep -> rows launched -> steps
    forms: Dict[object, Dict[str, int]] = {}   # rep -> attend -> steps
    token_bytes: Dict[object, int] = {}
    state: Dict[object, List[int]] = {}     # rep -> [decode steps, bytes]
    edges: Dict[object, List[float]] = {}
    compiling: Dict[object, bool] = {}   # tid -> inside a compiling call
    for r in records:
        name = r.get("name")
        leaf = worker_leaf(name)
        if leaf is None:
            continue
        attrs = r.get("attrs") or {}
        tid = r.get("tid", 0)
        if leaf == "dispatch":
            compiling[tid] = attrs.get("phase") == "compile"
        if attrs.get("role") == "drafter" or (
                leaf in CALL_LEAVES and compiling.get(tid)):
            continue
        rep = attrs.get("replica", 0)
        t0, dur = float(r.get("t0", 0.0)), float(r.get("dur", 0.0))
        per.setdefault(rep, {}).setdefault(name, []).append(dur)
        if "kv_positions_read" in attrs:
            acc = kv.setdefault(rep, {}).setdefault(name, [0, 0])
            acc[0] += int(attrs["kv_positions_read"])
            acc[1] += int(attrs.get("kv_positions_live", 0))
        if "cache_bytes_per_token" in attrs:
            token_bytes[rep] = int(attrs["cache_bytes_per_token"])
        if name == "decode.dispatch" and "state_bytes" in attrs:
            acc = state.setdefault(rep, [0, 0])
            acc[0] += 1
            acc[1] += int(attrs["state_bytes"])
        if name == "decode.dispatch" and "rows" in attrs:
            acc, rows = rungs.setdefault(rep, {}), int(attrs["rows"])
            acc[rows] = acc.get(rows, 0) + 1
        if name == "decode.dispatch" and "attend" in attrs:
            acc = forms.setdefault(rep, {})
            acc[attrs["attend"]] = acc.get(attrs["attend"], 0) + 1
        if name == "decode.fetch" and "bytes" in attrs:
            acc = fetched.setdefault(rep, [0, 0])
            acc[0] += 1
            acc[1] += int(attrs["bytes"])
        if name == "decode.fetch" and "expert_assignments" in attrs:
            acc = experts.setdefault(rep, [0, 0, 0, 0, 0])
            acc[0] += 1
            acc[1] += int(attrs["expert_assignments"])
            acc[2] += int(attrs.get("expert_tokens_max", 0))
            acc[3] += int(attrs.get("experts_idle", 0))
            acc[4] += int(attrs.get("expert_rows_computed", 0))
        e = edges.setdefault(rep, [t0, t0 + dur])
        e[0], e[1] = min(e[0], t0), max(e[1], t0 + dur)
    out: Dict[str, Dict] = {}
    for rep, leaves in sorted(per.items(), key=lambda kv: _bucket_key(kv[0])):
        steps = len(leaves.get("decode.dispatch", ()))
        wall = edges[rep][1] - edges[rep][0]
        waited = sum(sum(v) for k, v in leaves.items()
                     if k.endswith(".device_wait"))
        amplification = {}
        for key, call in (("kv_read_amplification", "decode.dispatch"),
                          ("chunk_kv_read_amplification", "chunk.dispatch")):
            read, live = kv.get(rep, {}).get(call, (0, 0))
            if live:
                amplification[key] = round(read / live, 4)
        if rep in experts:
            n, total, most, idle, rows = experts[rep]
            amplification["expert_load"] = {
                "assignments_per_step": round(total / n, 3),
                "busiest_expert_tokens_per_step": round(most / n, 3),
                "idle_experts_per_step": round(idle / n, 3)}
            if rows:
                amplification["expert_load"]["expert_fill"] = round(
                    total / rows, 4)
        if rep in rungs:
            amplification["decode_row_rungs"] = {
                str(rows): round(n / steps, 4)
                for rows, n in sorted(rungs[rep].items())}
        if rep in forms:
            amplification["decode_attend"] = {
                form: round(n / steps, 4)
                for form, n in sorted(forms[rep].items())}
        if rep in token_bytes:
            amplification["cache_bytes_per_token"] = token_bytes[rep]
        if rep in fetched:
            amplification["decode_fetch_bytes_per_step"] = round(
                fetched[rep][1] / fetched[rep][0], 1)
        if rep in state:
            n, total = state[rep]
            amplification["state_bytes_per_step"] = round(total / n, 1)
            read = kv.get(rep, {}).get("decode.dispatch", (0, 0))[0] \
                * token_bytes.get(rep, 0)
            if total + read:
                amplification["state_bytes_share"] = round(
                    total / (total + read), 4)
        out[str(rep)] = {
            "steps": steps, "wall_sec": round(wall, 6),
            "host_exposed_share": round((wall - waited) / wall, 4)
            if wall > 0 else None,
            "host_exposed_ms_per_step": round(
                1e3 * (wall - waited) / steps, 4) if steps else None,
            **amplification,
            "leaves": {
                name: {
                    "count": len(vals),
                    "ms_per_step": round(1e3 * sum(vals) / steps, 4)
                    if steps else None,
                    "mean_ms": round(1e3 * sum(vals) / len(vals), 4),
                    "p95_ms": round(1e3 * _percentile(sorted(vals), 95), 4),
                }
                for name, vals in sorted(leaves.items(),
                                         key=lambda kv: -sum(kv[1]))
            },
        }
    return out


def format_decode_table(by_replica: Dict) -> str:
    """``decode_host_phases`` as text (``trace_tpu.py summarize``)."""
    lines = []
    for rep, b in by_replica.items():
        share = b["host_exposed_share"]
        lines.append(
            f"decode worker, replica {rep}: {b['steps']} decode steps in "
            f"{b['wall_sec']:.3f}s; host-exposed "
            + (f"{share:.1%} of the round" if share is not None else "n/a")
            + (f" = {b['host_exposed_ms_per_step']:.3f} ms/step"
               if b["host_exposed_ms_per_step"] is not None else ""))
        if "kv_read_amplification" in b:
            lines.append(
                f"  KV read amplification per decode step: "
                f"{b['kv_read_amplification']:.3f} (positions the step's "
                "attention covers / positions live)"
                + (f"; per chunk launch "
                   f"{b['chunk_kv_read_amplification']:.3f}"
                   if "chunk_kv_read_amplification" in b else ""))
        if "decode_row_rungs" in b:
            lines.append("  decode steps by rows launched: " + ", ".join(
                f"{share:.1%} at {rows}"
                for rows, share in b["decode_row_rungs"].items()))
        if "decode_attend" in b:
            lines.append("  decode steps by attention: " + ", ".join(
                f"{share:.1%} {form}"
                for form, share in b["decode_attend"].items()))
        if "state_bytes_per_step" in b:
            lines.append(
                f"  recurrent state a decode step reads and writes: "
                f"{b['state_bytes_per_step'] / 2**20:.1f} MB"
                + (f" = {b['state_bytes_share']:.1%} of its per-stream "
                   "bytes (beside the cached positions its attention covers)"
                   if "state_bytes_share" in b else ""))
        if "expert_load" in b:
            e = b["expert_load"]
            lines.append(
                f"  expert load per decode step: "
                f"{e['assignments_per_step']:.1f} assignments to held "
                f"experts (all layers), the busiest expert's "
                f"{e['busiest_expert_tokens_per_step']:.1f}, "
                f"{e['idle_experts_per_step']:.2f} held experts idle"
                + (f"; {e['expert_fill']:.1%} of the rows their products "
                   "computed" if "expert_fill" in e else ""))
        if "cache_bytes_per_token" in b:
            lines.append(f"  cache bytes per token: "
                         f"{b['cache_bytes_per_token']}")
        if "decode_fetch_bytes_per_step" in b:
            ms = b["leaves"].get("decode.fetch", {}).get("mean_ms")
            lines.append(
                f"  fetched per decode step: "
                f"{b['decode_fetch_bytes_per_step']:.0f} bytes"
                + (f" in {ms:.3f} ms" if ms is not None else "")
                + " (decode.fetch)")
        header = (f"  {'leaf':<22} {'count':>7} {'ms/step':>10} "
                  f"{'mean_ms':>10} {'p95_ms':>10}")
        lines += [header, "  " + "-" * (len(header) - 2)]
        for name, s in b["leaves"].items():
            per_step = (f"{s['ms_per_step']:>10.3f}"
                        if s["ms_per_step"] is not None else f"{'n/a':>10}")
            lines.append(f"  {name:<22} {s['count']:>7d} {per_step} "
                         f"{s['mean_ms']:>10.3f} {s['p95_ms']:>10.3f}")
    return "\n".join(lines)


# ------------------------------------------------- the worker's round account

#: how many of the longest rounds the account prints whole
LONGEST_ROUNDS = 16
#: the seconds of rounds a worker's ``snapshot()`` accounts for: an
#: interval an exporter's scrape or a flight-recorder tick can compare
#: with the last, and a bound on what a snapshot costs the serving process
#: (PERF.md section 6, PR 40: by the window's rounds, not by the ring)
ACCOUNT_SECONDS = 30.0
_WAITS = tuple(k for k in ROUND_LEAVES if k.endswith(".wait_fetch"))


def round_rows(records: Sequence[Dict]) -> List[Dict]:
    """The rows of the ring of rounds that ``Tracer.flush`` wrote beside
    the spans (records named ``round``: the row is their attrs, the
    record's own — clock-aligned — ``t0`` its start)."""
    return [dict(r.get("attrs") or {}, t0=float(r.get("t0", 0.0)))
            for r in records
            if r.get("name") == ROUND_RECORD
            and "wall" in (r.get("attrs") or {})]


def _round_parts(row: Dict) -> Dict[str, float]:
    """One row's split, in seconds: the leaves it holds under their names
    (a call's ``wait_fetch`` = its wait for the device and the fetch,
    one barrier untraced), then ``other`` — they add up to ``wall``."""
    parts = {k: row[k] for k in ROUND_LEAVES if row.get(k)}
    parts["other"] = row.get("other", 0.0)
    return parts


def _ms(seconds: float) -> float:
    return round(1e3 * seconds, 4)


def round_account(rows: Sequence[Dict]) -> Dict:
    """Where the decode worker's rounds went, from rows of the ring of
    rounds (``Tracer.rounds(t0, t1)`` cuts them to a window;
    ``round_rows`` of a flushed file).  Per round KIND (``decode`` = one
    decode step and nothing else; ``prefill`` / ``chunk`` / ``verify`` =
    it also held such a call): ``count``, ``wall_ms`` p50 / p90 / p99 /
    max / mean, the ``mean_ms`` and ``p50_ms`` over the kind's rounds of
    every part (the leaves by ``decode_host_phases``' names, a call's two
    waits as its ``wait_fetch``; ``other``), ``builds`` / ``build_ms``
    (the executables JAX built inside them).  ``cpu``: the worker's own
    CPU time, from the rows that carry a reading of its thread's clock
    (``cpu`` over ``cpu_span``; the clock is read every tenth of a second,
    not every round) — the ``share`` of the read spans it was on its CPU,
    that share of a mean round as ``ms_per_round``, and
    ``host_off_cpu_ms_per_round`` = (``wall`` - every wait) a round less
    it: the time the worker wanted to run and did not (less the little
    CPU a wait itself burns).  ``longest``: the ``LONGEST_ROUNDS`` longest
    rounds whole, each with its split, its builds and, where it carries
    one, its reading of the CPU clock.  Milliseconds throughout;
    JSON-ready."""
    by_kind: Dict[str, List[Dict]] = {}
    for r in rows:
        by_kind.setdefault(r.get("kind", "decode"), []).append(r)
    kinds = {}
    for kind, rs in sorted(by_kind.items(), key=lambda kv: -len(kv[1])):
        walls = sorted(r["wall"] for r in rs)
        split = [_round_parts(r) for r in rs]
        names = sorted({k for p in split for k in p})
        kinds[kind] = {
            "count": len(rs),
            "wall_ms": {"p50": _ms(_percentile(walls, 50)),
                        "p90": _ms(_percentile(walls, 90)),
                        "p99": _ms(_percentile(walls, 99)),
                        "max": _ms(walls[-1]),
                        "mean": _ms(sum(walls) / len(walls))},
            "parts": {
                name: {"mean_ms": _ms(sum(vals) / len(vals)),
                       "p50_ms": _ms(_percentile(vals, 50))}
                for name in names
                for vals in [sorted(p.get(name, 0.0) for p in split)]},
            "builds": sum(int(r.get("builds", 0)) for r in rs),
            "build_ms": round(1e3 * sum(r.get("build_s", 0.0)
                                        for r in rs), 3),
        }
    wall = sum(r["wall"] for r in rows)
    span = sum(r.get("cpu_span", 0.0) for r in rows)
    cpu = {"span_sec": round(span, 6)}
    if span > 0.0:
        share = sum(r.get("cpu", 0.0) for r in rows) / span
        waited = sum(r.get(k, 0.0) for r in rows for k in _WAITS)
        on_cpu = share * wall / len(rows)
        cpu.update(share=round(share, 4), ms_per_round=_ms(on_cpu),
                   host_off_cpu_ms_per_round=_ms(
                       (wall - waited) / len(rows) - on_cpu))
    longest = sorted(rows, key=lambda r: -r["wall"])[:LONGEST_ROUNDS]
    return {
        "rounds": len(rows),
        "wall_sec": round(wall, 6),
        "builds": sum(k["builds"] for k in kinds.values()),
        "cpu": cpu,
        "kinds": kinds,
        "longest": [{
            "t0": round(r["t0"], 6), "replica": r.get("replica", 0),
            "round": r.get("round", 0), "kind": r.get("kind", "decode"),
            "live": r.get("live", 0), "seated": r.get("seated", 0),
            "wall_ms": _ms(r["wall"]),
            "parts_ms": {k: _ms(v) for k, v in _round_parts(r).items()},
            "cpu_ms": _ms(r.get("cpu", 0.0)),
            "cpu_span_ms": _ms(r.get("cpu_span", 0.0)),
            "builds": int(r.get("builds", 0)),
            "build_ms": round(1e3 * r.get("build_s", 0.0), 3),
        } for r in longest],
    }


def recent_round_account(tracer, replica: int) -> Dict:
    """``round_account`` of one worker's rounds that began in the last
    ``ACCOUNT_SECONDS`` (``window_sec``): what its ``snapshot()`` carries."""
    account = round_account(tracer.rounds(
        t0=tracer.clock() - ACCOUNT_SECONDS, replica=replica))
    account["window_sec"] = ACCOUNT_SECONDS
    return account


def format_round_table(by_replica: Dict) -> str:
    """``round_account`` per replica as text (``trace_tpu.py summarize``,
    under the host-phase table)."""
    lines = []
    for rep, acc in by_replica.items():
        lines.append(
            f"decode worker rounds, replica {rep}: {acc['rounds']} rounds in "
            f"{acc['wall_sec']:.3f}s; {acc['builds']} executables built "
            "inside them")
        cpu = acc["cpu"]
        if "share" in cpu:
            lines.append(
                f"  the worker on its CPU {cpu['share']:.1%} of "
                f"{cpu['span_sec']:.3f}s read = {cpu['ms_per_round']:.3f} ms "
                f"a round; host_off_cpu "
                f"{cpu['host_off_cpu_ms_per_round']:.3f} ms a round")
        for kind, k in acc["kinds"].items():
            w = k["wall_ms"]
            lines.append(
                f"  {kind} rounds: {k['count']}; wall ms p50 {w['p50']:.3f} "
                f"p90 {w['p90']:.3f} p99 {w['p99']:.3f} max {w['max']:.3f}"
                + (f"; {k['builds']} builds, {k['build_ms']:.1f} ms"
                   if k["builds"] else ""))
            header = f"    {'part':<22} {'mean_ms':>10} {'p50_ms':>10}"
            lines += [header, "    " + "-" * (len(header) - 4)]
            for name, s in sorted(k["parts"].items(),
                                  key=lambda kv: -kv[1]["mean_ms"]):
                lines.append(f"    {name:<22} {s['mean_ms']:>10.3f} "
                             f"{s['p50_ms']:>10.3f}")
        lines.append(f"  the {len(acc['longest'])} longest rounds:")
        for r in acc["longest"]:
            parts = ", ".join(
                f"{k} {v:.3f}" for k, v in sorted(
                    r["parts_ms"].items(), key=lambda kv: -kv[1]) if v)
            lines.append(
                f"    round {r['round']} ({r['kind']}, {r['live']} live, "
                f"{r['seated']} seated) {r['wall_ms']:.3f} ms: {parts}"
                + (f"; on its CPU {r['cpu_ms']:.1f} of the "
                   f"{r['cpu_span_ms']:.1f} ms that end with it"
                   if r["cpu_span_ms"] else "")
                + (f"; {r['builds']} builds, {r['build_ms']:.1f} ms"
                   if r["builds"] else ""))
    return "\n".join(lines)
