"""Device (HBM) memory accounting — the missing input for every
memory-budget decision.

``jax.local_devices()[i].memory_stats()`` exposes the allocator's live
counters on TPU/GPU backends (``bytes_in_use``, ``peak_bytes_in_use``,
``bytes_limit``); on CPU it returns ``None``/raises.  This module wraps it
with the repo's telemetry discipline:

- :func:`device_memory_stats` — one host-side read per device, graceful
  ``None`` where the backend does not support it (CPU tests run every
  caller unchanged);
- :class:`MemorySampler` — a cheap sampler recording bytes-in-use/peak at
  phase boundaries: attach :meth:`feed` as a tracer listener and every
  ``device_block``/``eval``/``ckpt_save`` record triggers a sample tagged
  with that phase (the trainer wiring), or call :meth:`sample` explicitly
  per executed batch (the serve-engine wiring).  Samples optionally land
  in the trace as zero-duration ``"hbm"`` records so the step-breakdown
  table, merged multi-rank traces and ``trace_tpu.py summarize`` carry the
  memory columns offline too.  An unsupported backend flips
  ``supported=False`` on the FIRST attempt and every later call is a
  single attribute read — the no-op contract;
- :meth:`MemorySampler.beat_payload` — the ``hbm``/``hbm_peak`` fields the
  watchdog heartbeat carries so ``GangMonitor.status_line()`` can report
  peak HBM per rank without touching the device stream.

Reads are pure host calls against the allocator's counters — no dispatch,
no sync — so sampling at phase boundaries cannot perturb the step loop.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

#: tracer record name for memory samples (zero-duration, like ``hop``)
HBM_RECORD = "hbm"

#: phase records whose arrival triggers a listener-driven sample — the
#: boundaries where memory can have moved: the step's completion barrier,
#: the in-loop eval, and the checkpoint snapshot
SAMPLE_ON = ("device_block", "eval", "ckpt_save", "ckpt_wait")


def gb(nbytes: Optional[float]) -> Optional[float]:
    """Bytes -> GiB, rounded for tables/JSON (None passes through)."""
    return None if nbytes is None else round(float(nbytes) / 2**30, 3)


def device_memory_stats(devices: Optional[Sequence] = None
                        ) -> Optional[List[Dict]]:
    """Per-device allocator counters, or None where unsupported.

    ``devices`` defaults to ``jax.local_devices()``; a backend whose
    ``memory_stats()`` raises or returns nothing (CPU) yields None — the
    graceful-no-op contract every caller relies on."""
    try:
        if devices is None:
            import jax

            devices = jax.local_devices()
        out = []
        for d in devices:
            stats = d.memory_stats()
            if not stats:
                return None
            in_use = int(stats.get("bytes_in_use", 0))
            out.append({
                "device": int(getattr(d, "id", len(out))),
                "bytes_in_use": in_use,
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use", in_use)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            })
        return out or None
    except Exception:  # noqa: BLE001 — unsupported backend = no-op
        return None


def memory_snapshot(devices: Optional[Sequence] = None) -> Dict:
    """One-shot JSON-ready snapshot (the serve/exporter building block)."""
    stats = device_memory_stats(devices)
    if stats is None:
        return {"supported": False}
    in_use = sum(s["bytes_in_use"] for s in stats)
    peak = sum(s["peak_bytes_in_use"] for s in stats)
    return {
        "supported": True,
        "devices": stats,
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "device_peak_bytes": max(s["peak_bytes_in_use"] for s in stats),
        "gb_in_use": gb(in_use),
        "gb_peak": gb(peak),
    }


class KVBudgetExceeded(RuntimeError):
    """A generative stream (or a decode engine's cache preallocation)
    would exceed the declared ``--kv_hbm_mb`` KV budget — the LOUD
    refusal that replaces an allocator OOM three layers deeper."""


class KVBudget:
    """Declared KV-cache HBM budget for one decode engine.

    The decode engine preallocates its page pools ONCE (``[L, n_pages,
    page_sz, width]`` a pool, donated across steps — decode never
    allocates), so the budget decision happens at two doors, both loud:

    - **construction**: :meth:`cap_pages` returns how many pages the
      declared budget actually covers — the engine allocates THAT many
      (stderr-noted when capped below the request) and refuses outright
      (:class:`KVBudgetExceeded`) when not even one maximum-length stream
      fits;
    - **admission**: a stream whose worst-case footprint (``prompt +
      max_new_tokens`` positions) passes its page table's extent is
      refused in the budget's units
      (``PagedDecodeEngine.check_stream_admissible``;
      :meth:`check_stream` is the same door for a caller without an
      engine) — the caller gets the budget math, not a mid-decode OOM.

    Live occupancy (:meth:`set_live` / :attr:`live_bytes`) is the
    ``/metrics`` gauge: positions actually WRITTEN across live slots ×
    bytes per position — what the cache holds now, not the preallocation.
    ``budget_bytes=None`` (no ``--kv_hbm_mb``) disables every check and
    keeps only the gauge."""

    def __init__(self, budget_mb: Optional[float] = None):
        self.budget_bytes: Optional[int] = (
            None if not budget_mb else int(float(budget_mb) * 2**20))
        self._live = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------- doors
    def cap_pages(self, requested: int, page_bytes: int,
                  min_pages: int = 1, reserved: int = 0) -> int:
        """Construction door (``serve.kvpage``): how many
        fixed-size KV pages the declared budget covers (= ``requested``
        when unbudgeted).  ``min_pages`` is the floor the engine needs to
        hold ONE maximum-length stream — a budget that cannot cover it
        refuses loudly here instead of deadlocking every claim.
        ``reserved``: bytes the engine allocates whatever the traffic (the
        slots' recurrent state), paid from the budget before any page.  The
        page ALLOCATION ledger itself lives in
        :class:`pdnlp_tpu.serve.kvpage.PageAllocator`; this budget only
        sizes the pool."""
        if self.budget_bytes is None:
            return int(requested)
        fit = max(0, self.budget_bytes - int(reserved)) \
            // max(1, int(page_bytes))
        if fit < int(min_pages):
            state = (f" beside {reserved / 2**20:.1f} MB of per-slot state"
                     if reserved else "")
            raise KVBudgetExceeded(
                f"kv_hbm_mb={self.budget_bytes / 2**20:.1f} covers only "
                f"{fit} KV pages ({page_bytes / 2**20:.2f} MB/page){state} but "
                f"one maximum-length stream needs {min_pages} — raise "
                "--kv_hbm_mb or shrink --decode_max_len/--kv_page_sz")
        return min(int(requested), int(fit))

    def check_stream(self, tokens_total: int, token_bytes: int) -> None:
        """Admission door: refuse a stream whose worst-case KV cannot fit
        under the budget (prompt + max_new positions × bytes/position)."""
        if self.budget_bytes is None:
            return
        need = int(tokens_total) * int(token_bytes)
        if need > self.budget_bytes:
            raise KVBudgetExceeded(
                f"stream needs {need / 2**20:.1f} MB of KV "
                f"({tokens_total} positions) but the declared budget is "
                f"{self.budget_bytes / 2**20:.1f} MB (--kv_hbm_mb) — "
                "shorten the prompt / max_new_tokens or raise the budget")

    # ------------------------------------------------------------- gauge
    def set_live(self, nbytes: int) -> None:
        with self._lock:
            self._live = int(nbytes)

    @property
    def live_bytes(self) -> int:
        with self._lock:
            return self._live

    def snapshot(self) -> Dict:
        """JSON-ready block for engine snapshots / the live exporter."""
        with self._lock:
            live = self._live
        return {
            "budget_mb": (None if self.budget_bytes is None
                          else round(self.budget_bytes / 2**20, 3)),
            "live_bytes": live,
            "live_mb": round(live / 2**20, 3),
        }


class MemorySampler:
    """Phase-boundary HBM sampler (module docstring).

    ``devices=None`` samples every local device; the serve engine passes
    its mesh slice so per-replica accounting covers only the devices that
    replica owns.  ``tracer`` (optional): samples additionally land as
    ``"hbm"`` records so offline trace tooling sees them.
    ``min_interval_s`` rate-limits listener-driven sampling (0 = every
    boundary — the reads are allocator-counter lookups, not syncs)."""

    def __init__(self, devices: Optional[Sequence] = None, *,
                 tracer=None, min_interval_s: float = 0.0,
                 clock: Callable[[], float] = time.monotonic):
        self._devices = list(devices) if devices is not None else None
        self._tracer = tracer
        self._min_interval = float(min_interval_s)
        self._clock = clock
        # samples land from listener/worker threads while the live
        # exporter snapshots from the HTTP thread — state mutations and
        # the per_phase iteration must not race
        self._lock = threading.Lock()
        self._last_t: Optional[float] = None
        self.supported: Optional[bool] = None  # unknown until first sample
        self.bytes_in_use = 0
        self.peak_bytes = 0          # max over samples of summed peaks
        self.device_peak_bytes = 0   # max single-device peak (the HBM
        #                              budget number per chip)
        self.samples = 0
        self.per_phase: Dict[str, Dict[str, int]] = {}
        self._last_devices: Optional[List[Dict]] = None

    # ------------------------------------------------------------ sampling
    def sample(self, phase: Optional[str] = None,
               force: bool = False) -> Optional[Dict]:
        """Read the allocator counters once; returns the aggregate dict or
        None (unsupported / rate-limited).  ``phase`` tags the per-phase
        peak table."""
        if self.supported is False:
            return None
        now = self._clock()
        if not force and self._min_interval and self._last_t is not None \
                and (now - self._last_t) < self._min_interval:
            return None
        stats = device_memory_stats(self._devices)
        if stats is None:
            self.supported = False
            return None
        in_use = sum(s["bytes_in_use"] for s in stats)
        peak = sum(s["peak_bytes_in_use"] for s in stats)
        dev_peak = max(s["peak_bytes_in_use"] for s in stats)
        with self._lock:
            self.supported = True
            self._last_t = now
            self.samples += 1
            self._last_devices = stats
            self.bytes_in_use = in_use
            self.peak_bytes = max(self.peak_bytes, peak)
            self.device_peak_bytes = max(self.device_peak_bytes, dev_peak)
            if phase:
                p = self.per_phase.setdefault(
                    phase,
                    {"bytes_in_use": 0, "peak_bytes": 0, "samples": 0})
                p["bytes_in_use"] = max(p["bytes_in_use"], in_use)
                p["peak_bytes"] = max(p["peak_bytes"], peak)
                p["samples"] += 1
        agg = {"bytes_in_use": in_use, "peak_bytes": peak,
               "device_peak_bytes": dev_peak}
        tr = self._tracer
        if tr is not None and tr.enabled:
            t = tr.now()
            tr.record(HBM_RECORD, t, t, phase=phase, **agg)
        return agg

    def feed(self, record: Dict) -> None:
        """Tracer-listener form: sample at phase boundaries
        (:data:`SAMPLE_ON` records).  Ignores everything else — including
        the ``hbm`` records its own samples emit."""
        if record.get("name") in SAMPLE_ON:
            self.sample(phase=record["name"])

    # ------------------------------------------------------------ reporting
    def snapshot(self, sample: bool = True) -> Dict:
        """JSON-ready state; ``sample=True`` refreshes the counters first
        so an exporter scrape reads NOW, not the last phase boundary."""
        if sample:
            self.sample(force=True)
        with self._lock:
            if not self.supported:
                return {"supported": False}
            return {
                "supported": True,
                "bytes_in_use": self.bytes_in_use,
                "peak_bytes_in_use": self.peak_bytes,
                "device_peak_bytes": self.device_peak_bytes,
                "gb_in_use": gb(self.bytes_in_use),
                "gb_peak": gb(self.peak_bytes),
                "samples": self.samples,
                "per_phase": {
                    phase: {**p, "gb_peak": gb(p["peak_bytes"])}
                    for phase, p in sorted(self.per_phase.items())
                },
                "devices": self._last_devices,
            }

    def beat_payload(self) -> Dict:
        """The heartbeat's memory fields (empty where unsupported) — how
        peak HBM per rank reaches ``GangMonitor.status_line()``."""
        if not self.supported:
            return {}
        return {"hbm": self.bytes_in_use, "hbm_peak": self.peak_bytes}
