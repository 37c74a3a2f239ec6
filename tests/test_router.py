"""Replica-router tests: tiered admission, least-loaded dispatch,
eject/requeue with preserved deadline budgets, warmup-gated reintegration,
rolling-swap rollback on a corrupt manifest, hedging — on fake engines with
injected clocks — plus one real-engine chaos pass and, ``slow``-marked, a
SIGTERM'd ``serve_tpu.py`` graceful-shutdown case."""
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from pdnlp_tpu.obs.trace import Tracer  # noqa: E402
from pdnlp_tpu.serve import (  # noqa: E402
    AdmissionControl, DeadlineExceeded, LoadShedError, QueueFullError,
    ReplicaRouter, ServeMetrics,
)
from pdnlp_tpu.serve.batcher import _Request  # noqa: E402
from pdnlp_tpu.train import checkpoint as ckpt  # noqa: E402

from tests.test_elastic import FakeClock  # noqa: E402


class FakeEngine:
    """Engine-shaped test double: instant host-side 'forwards', recorded
    calls, real checkpoint-manifest loading (so corrupt artifacts raise the
    REAL CorruptCheckpointError)."""

    def __init__(self, num_labels=6, latency=0.0):
        self.args = SimpleNamespace(max_seq_len=128)
        self.tokenizer = SimpleNamespace(
            cls_id=2, sep_id=3, pad_id=0,
            encode_ids=lambda text, n: [2] * min(max(len(text), 2), n))
        self.metrics = ServeMetrics()
        self.tracer = Tracer(enabled=False)
        self.span_attrs = {}
        self.checkpoint_path = None
        self.num_labels = num_labels
        self.latency = latency
        self.calls = []

    def pad_rows(self, n):
        return int(n)

    def infer_ids(self, id_lists, seq, rows=0, request_ids=None):
        if self.latency:
            time.sleep(self.latency)
        self.calls.append((len(id_lists), int(seq)))
        self.metrics.retraces  # noqa: B018 — engine metrics shape parity
        return np.full((len(id_lists), self.num_labels), float(seq),
                       np.float32)

    def load_checkpoint(self, path):
        ckpt.load_raw(path)  # real manifest verification
        self.checkpoint_path = path


def _router(n=2, *, start=True, clock=None, **kw):
    engines = [FakeEngine() for _ in range(n)]
    kw.setdefault("buckets", (32, 64))
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_wait_ms", 2.0)
    kw.setdefault("stall_timeout", 1.0)
    kw.setdefault("poll_interval", 0.02)
    if clock is not None:
        kw["clock"] = clock
    r = ReplicaRouter(engines, **kw)
    if start:
        r.start()
        assert r.wait_ready(10)
    return r, engines


# ----------------------------------------------------------- admission tiers
def test_admission_tier_ladder_with_injected_clock():
    clk = FakeClock()
    adm = AdmissionControl(16, backpressure_at=8, shed_at=12,
                           shed_slack_ms=10.0, clock=clk)
    assert adm.tier(0) == "healthy"
    assert adm.tier(7) == "healthy"
    assert adm.tier(8) == "backpressure"
    assert adm.tier(11) == "backpressure"
    assert adm.tier(12) == "shed"
    assert adm.tier(15) == "shed"
    assert adm.tier(16) == "reject"
    with pytest.raises(ValueError):  # thresholds must be ordered
        AdmissionControl(8, backpressure_at=7, shed_at=3)


def test_shed_picks_lowest_deadline_slack_first():
    clk = FakeClock()
    adm = AdmissionControl(8, shed_slack_ms=50.0, clock=clk)

    def req(deadline):
        return _Request([2, 3], 32, deadline)

    roomy = req(clk() + 10.0)       # 10s slack: viable
    tight = req(clk() + 0.030)      # 30ms slack: doomed
    tighter = req(clk() + 0.010)    # 10ms slack: doomed, drops FIRST
    free = req(None)                    # deadline-free: never shed
    victims = adm.shed_victims([roomy, tight, free], arriving=tighter)
    assert victims == [tighter, tight]
    # backpressure wait is capped by the request's own slack
    assert adm.backpressure_wait_sec(tighter) <= 0.010 + 1e-9
    assert adm.backpressure_wait_sec(free) == \
        adm.backpressure_wait_ms / 1e3


def test_router_walks_all_tiers_healthy_to_reject():
    # nothing can flush (size 100, wait 60s): depth is submit-controlled
    r, _ = _router(n=2, max_batch_size=100, max_wait_ms=60_000.0,
                   max_queue=8, backpressure_at=4, shed_at=6,
                   backpressure_wait_ms=5.0, shed_slack_ms=20.0)
    try:
        for _ in range(4):
            r.submit_ids([2, 3], deadline_ms=60_000)
        assert r.metrics.backpressure_waits_total.value == 0
        r.submit_ids([2, 3], deadline_ms=60_000)  # depth 4: bounded wait
        assert r.metrics.backpressure_waits_total.value == 1
        r.submit_ids([2, 3], deadline_ms=60_000)  # depth 5: still bp tier
        # depth 6 = shed tier: a viable-slack arrival is admitted...
        r.submit_ids([2, 3], deadline_ms=60_000)
        # ...a doomed one (slack under the 20ms floor) is shed on arrival
        with pytest.raises(LoadShedError):
            r.submit_ids([2, 3], deadline_ms=5.0)
        assert r.metrics.shed_total.value == 1
        r.submit_ids([2, 3], deadline_ms=60_000)  # depth 7
        with pytest.raises(QueueFullError):      # depth 8 = hard reject
            r.submit_ids([2, 3], deadline_ms=60_000)
        assert r.metrics.rejected_total.value == 1
    finally:
        r.stop(drain=False)


def test_shed_evicts_queued_lowest_slack_not_just_arrivals():
    clk = FakeClock()
    r, _ = _router(n=1, start=False, clock=clk, max_batch_size=100,
                   max_wait_ms=60_000.0, max_queue=8, backpressure_at=2,
                   shed_at=2, shed_slack_ms=50.0)
    r._started = True  # white-box: no workers, queue mechanics only
    doomed = r.submit_ids([2, 3], deadline_ms=40.0)   # 40ms < 50ms floor
    roomy = r.submit_ids([2, 3], deadline_ms=60_000)
    # depth 2 = shed tier: the next submit sweeps the pool and drops the
    # lowest-slack QUEUED request, admitting the viable arrival
    fresh = r.submit_ids([2, 3], deadline_ms=60_000)
    with pytest.raises(LoadShedError):
        doomed.result(timeout=0)
    assert not roomy.done() and not fresh.done()
    assert r.metrics.shed_total.value == 1


# ------------------------------------------------------ least-loaded dispatch
def test_least_loaded_dispatch_balances_queues():
    clk = FakeClock()
    r, _ = _router(n=3, start=False, clock=clk, max_batch_size=100,
                   max_wait_ms=60_000.0, max_queue=100)
    r._started = True
    for _ in range(9):
        r.submit_ids([2, 3], deadline_ms=60_000)
    loads = [s.replica.load() for s in r._slots]
    assert loads == [3, 3, 3]  # round-robin emerges from least-loaded


# ------------------------------------------------- eject / requeue / deadline
def test_eject_requeues_within_deadline_budget():
    clk = FakeClock()
    r, _ = _router(n=2, start=False, clock=clk, max_batch_size=100,
                   max_wait_ms=60_000.0, max_queue=100, max_retries=1)
    r._started = True
    alive = r.submit_ids([2, 3], deadline_ms=60_000)
    expired = r.submit_ids([2, 3], deadline_ms=100.0)
    # force both onto replica 0 (white-box: dispatch spread them)
    q0 = r._slots[0].replica.queues
    q1 = r._slots[1].replica.queues
    for q in q1.values():
        for req in q:
            q0[req.bucket].append(req)
        q.clear()
    inflight = r.submit_ids([2, 3], deadline_ms=60_000)
    for q in q1.values():
        q.clear()
    r._slots[0].replica.inflight = [inflight]
    clk.advance(0.2)  # `expired`'s budget is gone; the others have plenty
    r._eject(0, "stalled")
    assert r._slots[0].replica.state == "ejected"
    with pytest.raises(DeadlineExceeded):
        expired.result(timeout=0)
    # survivors hold the still-live requests, budgets intact
    q1_reqs = [req for q in q1.values() for req in q]
    assert alive in q1_reqs and inflight in q1_reqs
    assert alive.deadline == pytest.approx(clk() + 60.0, abs=1.0)
    assert inflight.retries == 1          # in-flight work counts a retry
    assert r.metrics.requeued_total.value == 1   # queued work: a requeue
    assert r.metrics.retries_total.value == 1
    assert r.metrics.ejections_total.value == 1


def test_eject_exhausted_retry_budget_fails_loudly():
    clk = FakeClock()
    r, _ = _router(n=2, start=False, clock=clk, max_batch_size=100,
                   max_wait_ms=60_000.0, max_retries=0)
    r._started = True
    req = r.submit_ids([2, 3], deadline_ms=60_000)
    rep = next(s.replica for s in r._slots
               if any(req in q for q in s.replica.queues.values()))
    for q in rep.queues.values():
        q.clear()
    rep.inflight = [req]
    r._eject(rep.index, "crashed")
    with pytest.raises(Exception, match="retry budget"):
        req.result(timeout=0)


def test_crash_mid_traffic_zero_lost_and_relaunch_reintegrates():
    """End-to-end on fake engines with real workers: kill -> monitor eject
    -> requeue onto the survivor -> every accepted request completes ->
    relaunch runs the warmup probe BEFORE serving."""
    r, engines = _router(n=2, max_batch_size=2, max_wait_ms=5.0,
                         stall_timeout=0.5)
    try:
        futs = [r.submit_ids([2, 3, 4], deadline_ms=30_000)
                for _ in range(12)]
        r.kill_replica(0, "crash")
        outs = [f.result(timeout=30) for f in futs]
        assert all(o.shape == (6,) for o in outs)  # ZERO lost
        deadline = time.monotonic() + 10
        while r.states[0] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.states[0] == "ejected"
        assert r.metrics.ejections_total.value == 1

        fresh = FakeEngine()
        r.relaunch(0, engine=fresh)
        assert r.wait_ready(10)
        # warmup-gated reintegration: one probe per bucket ran BEFORE any
        # traffic could reach the fresh engine
        assert fresh.calls[: len(r.buckets)] == \
            [(1, b) for b in r.buckets]
        assert r.metrics.reintegrations_total.value == 1
        assert r.metrics.recovery_sec.snapshot()["count"] == 1
        assert r.submit_ids([2, 3], deadline_ms=30_000)\
                .result(timeout=10) is not None
    finally:
        r.stop(drain=False)


def test_stalled_replica_ejected_by_heartbeat_staleness():
    """The hang shape: worker wedges holding its batch, beats stop, the
    GangMonitor's stall verdict (not a crash code) drives the ejection and
    the wedged batch is retried on the survivor."""
    r, _ = _router(n=2, max_batch_size=2, max_wait_ms=5.0,
                   stall_timeout=0.4, poll_interval=0.05)
    try:
        r.kill_replica(0, "hang")
        futs = [r.submit_ids([2, 3, 4], deadline_ms=30_000)
                for _ in range(8)]
        outs = [f.result(timeout=30) for f in futs]
        assert all(o is not None for o in outs)
        deadline = time.monotonic() + 10
        while r.states[0] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.states[0] == "ejected"
    finally:
        r.stop(drain=False)


# ------------------------------------------------------------- rolling swap
def test_relaunch_after_stall_survives_the_stale_beat(tmp_path):
    """Regression: the dead incarnation's beat file is >= stall_timeout
    old when relaunch() runs — without a fresh beat landing BEFORE the
    slot flips live, the monitor's next poll reads the stale age against
    the new (alive) adapter and falsely ejects the newcomer mid-warmup."""
    r, _ = _router(n=2, max_batch_size=2, max_wait_ms=5.0,
                   stall_timeout=0.3, poll_interval=0.02)
    try:
        r.kill_replica(0, "hang")  # beats stop -> stall-shaped ejection
        deadline = time.monotonic() + 10
        while r.states[0] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.states[0] == "ejected"
        r.relaunch(0, engine=FakeEngine())
        assert r.wait_ready(10)
        # the newcomer must SURVIVE several monitor polls and serve
        time.sleep(10 * r.poll_interval)
        assert r.states[0] == "healthy"
        assert r.metrics.ejections_total.value == 1  # no false re-eject
        assert r.metrics.reintegrations_total.value == 1
    finally:
        r.stop(drain=False)


def test_rolling_swap_and_corrupt_manifest_rollback(tmp_path):
    r, engines = _router(n=2)
    try:
        good = str(tmp_path / "good-cls.msgpack")
        ckpt.save(good, {"w": np.ones(4, np.float32)})
        report = r.swap_checkpoint(good)
        assert report["swapped"] == [0, 1] and not report["rolled_back"]
        assert all(e.checkpoint_path == good for e in engines)
        assert r.metrics.swaps_total.value == 2

        bad = str(tmp_path / "bad-cls.msgpack")
        ckpt.save(bad, {"w": np.ones(4, np.float32)})
        with open(bad, "r+b") as f:  # corrupt: manifest verify must fail
            f.truncate(8)
        report = r.swap_checkpoint(bad)
        assert report["rolled_back"] == [0]
        assert report["swapped"] == []  # rollout ABORTED: pool unpoisoned
        assert "CorruptCheckpointError" in report["error"]
        assert all(e.checkpoint_path == good for e in engines)
        assert r.states == {0: "healthy", 1: "healthy"}
        assert r.metrics.swap_rollbacks_total.value == 1
        # the pool still serves
        assert r.submit_ids([2, 3], deadline_ms=10_000)\
                .result(timeout=10) is not None
    finally:
        r.stop(drain=False)


def test_relaunch_loads_the_pools_current_checkpoint(tmp_path):
    good = str(tmp_path / "pool-cls.msgpack")
    ckpt.save(good, {"w": np.zeros(2, np.float32)})
    r, _ = _router(n=2, checkpoint_path=good)
    try:
        r.kill_replica(1, "crash")
        deadline = time.monotonic() + 10
        while r.states[1] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        fresh = FakeEngine()
        r.relaunch(1, engine=fresh)
        assert r.wait_ready(10)
        assert fresh.checkpoint_path == good  # loaded during warmup
    finally:
        r.stop(drain=False)


# ------------------------------------------------------------------ hedging
def test_tail_hedging_duplicates_slow_queue_first_completion_wins():
    r, engines = _router(n=2, max_batch_size=100, max_wait_ms=60_000.0,
                         hedge_ms=30.0, poll_interval=0.01)
    try:
        with r._lock:  # park replica 1's queue behind a fake backlog so
            # replica 0 is strictly less loaded when the hedge scan runs
            blockers = [_Request([2, 3], 32, None) for _ in range(3)]
            for b in blockers:
                r._slots[1].replica.queues[32].append(b)
                r._pending += 1
            req = _Request([2, 3], 32, r.clock() + 30.0)
            r._slots[1].replica.queues[32].append(req)
            r._pending += 1
        deadline = time.monotonic() + 5
        while not r.metrics.hedges_total.value \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.metrics.hedges_total.value >= 1
        assert req.hedged
        # the copy landed on the less-loaded replica 0
        assert req in r._slots[0].replica.queues[32]
    finally:
        r.stop(drain=False)


def test_request_result_times_out_from_its_own_deadline():
    """Satellite: result() must not block forever when a deadline exists
    and nothing ever completes the request (dead worker shape)."""
    req = _Request([2, 3], 32, time.monotonic() - 1.0)  # already past
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        req.result()  # no explicit timeout: derived from the deadline
    from pdnlp_tpu.serve.batcher import RESULT_GRACE_SEC

    assert time.monotonic() - t0 <= RESULT_GRACE_SEC + 2.0


def test_batcher_expires_requests_at_dequeue_time(tok_engine=None):
    """Satellite: a request whose deadline passes between the flush
    decision and execution is deadline-failed, never executed."""
    eng = FakeEngine()
    from pdnlp_tpu.serve.batcher import DynamicBatcher

    b = DynamicBatcher.__new__(DynamicBatcher)
    b.engine = eng
    b.metrics = eng.metrics
    b.max_batch_size = 4
    req = _Request([2, 3], 32, time.monotonic() - 0.001)  # just expired
    live = _Request([2, 3], 32, time.monotonic() + 30.0)
    b._execute([req, live])
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=0)
    assert live.done() and live.result(timeout=0) is not None
    assert eng.calls == [(1, 32)]  # the expired row never rode the batch
    assert eng.metrics.deadline_expired_total.value == 1


# ---------------------------------------------------- per-replica phase obs
def test_trace_serve_by_replica_tables():
    from pdnlp_tpu.obs.phases import StepBreakdown

    bd = StepBreakdown()
    for rep, dur in ((0, 0.010), (0, 0.012), (1, 0.200)):
        bd.feed({"name": "forward", "t0": 0.0, "dur": dur, "tid": 0,
                 "depth": 0, "attrs": {"replica": rep, "seq": 64}})
    bd.feed({"name": "queue_wait", "t0": 0.0, "dur": 0.005, "tid": 0,
             "depth": 0, "attrs": {"replica": 1, "retry": 2}})
    bd.feed({"name": "swap", "t0": 0.0, "dur": 0.050, "tid": 0,
             "depth": 0, "attrs": {"replica": 0}})
    s = bd.summary()["serve_by_replica"]
    assert s["0"]["phases"]["forward"]["count"] == 2
    assert s["0"]["phases"]["swap"]["count"] == 1
    assert s["1"]["phases"]["forward"]["mean_sec"] == pytest.approx(0.2)
    assert s["1"]["retries"] == 2
    from pdnlp_tpu.obs.phases import format_table

    table = format_table(bd.summary())
    assert "replica 0" in table and "replica 1" in table


# ------------------------------------------------------- real-engine chaos
@pytest.mark.usefixtures("ndev")
def test_real_engines_kill_swap_and_zero_retraces(tmp_path):
    """One real pass over tiny engines: kill + relaunch + rolling swap
    under traffic, zero post-warmup retraces, zero lost requests."""
    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.models import bert  # noqa: F401 — engine dep
    from pdnlp_tpu.serve import InferenceEngine
    from pdnlp_tpu.utils.config import Args

    texts = ["天地人你我", "好坏大小上下来去", "高兴悲伤讨厌"]
    tok = WordPieceTokenizer(build_vocab(texts, size=128))

    def factory(i):
        return InferenceEngine(Args(model="bert-tiny"), tokenizer=tok,
                               mesh=None)

    r = ReplicaRouter([factory(0), factory(1)], engine_factory=factory,
                      buckets=(32,), max_batch_size=2, max_wait_ms=10.0,
                      stall_timeout=1.0, poll_interval=0.05)
    r.start()
    assert r.wait_ready(300)
    try:
        futs = [r.submit(texts[i % 3], deadline_ms=60_000)
                for i in range(10)]
        r.kill_replica(1, "crash")
        outs = [f.result(timeout=60) for f in futs]
        assert all(o.shape == (6,) for o in outs)

        swap = str(tmp_path / "swap-cls.msgpack")
        ckpt.save_params(swap, {"params": jax.device_get(
            r.engine(0).params)})
        deadline = time.monotonic() + 15
        while r.states[1] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.02)
        r.relaunch(1)
        assert r.wait_ready(300)
        report = r.swap_checkpoint(swap)
        assert sorted(report["swapped"]) == [0, 1]
        futs = [r.submit(texts[i % 3], deadline_ms=60_000)
                for i in range(6)]
        assert all(f.result(timeout=60) is not None for f in futs)
        assert r.retraces_post_warmup == 0  # kill+relaunch+swap: no trace
    finally:
        r.stop(drain=False)


# --------------------------------------------- real-process chaos (slow)
@pytest.mark.slow
def test_serve_tpu_sigterm_drains_and_flushes(tmp_path, corpus_path):
    """Satellite: SIGTERM mid-stream -> the server drains its in-flight
    window (answers for every accepted line), writes the metrics snapshot
    and the trace span file, and exits 0 — nothing silently dropped."""
    metrics_path = tmp_path / "serve_metrics.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "serve_tpu.py"),
         "--model", "bert-tiny", "--no_mesh", "--buckets", "32",
         "--data_path", str(corpus_path),
         "--vocab_path", str(tmp_path / "vocab.txt"),
         "--output_dir", str(tmp_path / "out"),
         "--metrics_path", str(metrics_path),
         "--trace", "true", "--trace_dir", str(tmp_path / "trace")],
        cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        # wait for readiness (warmup done) before feeding traffic
        deadline = time.monotonic() + 300
        ready = []

        def pump():
            for line in proc.stderr:
                ready.append(line)
                if "ready" in line:
                    return

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        while t.is_alive() and time.monotonic() < deadline:
            t.join(0.2)
        assert any("ready" in line for line in ready), "".join(ready)[-2000:]
        for text in ("天地人", "好坏大小", "高兴悲伤"):
            proc.stdin.write(text + "\n")
        proc.stdin.flush()
        time.sleep(1.0)
        proc.terminate()  # SIGTERM: graceful path, not a kill
        stdout, stderr = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, stderr[-3000:]
    answered = [line for line in stdout.splitlines() if "\t" in line]
    assert len(answered) == 3, stdout  # every accepted line got an answer
    assert metrics_path.exists()  # telemetry flushed on the signal path
    snap = json.loads(metrics_path.read_text())
    assert snap["requests_total"] >= 3
    trace_files = list((tmp_path / "trace").glob("trace_proc*.jsonl"))
    assert trace_files, "trace spans not flushed on shutdown"


# ---------------------------------------------- threadlint fix regressions
class _OwnerLock:
    """Lock proxy that records the owning thread — Condition-compatible,
    so tests can assert 'this thread does NOT hold the pool lock here'
    without the ambiguity of Lock.locked() (which any thread trips)."""

    def __init__(self):
        self._l = threading.Lock()
        self.owner = None

    def acquire(self, *a, **kw):
        got = self._l.acquire(*a, **kw)
        if got:
            self.owner = threading.get_ident()
        return got

    def release(self):
        self.owner = None
        self._l.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def test_relaunch_does_no_file_io_under_the_pool_lock(monkeypatch):
    """threadlint T3 regression: replica construction and the
    pre-install beat both write heartbeat files — relaunch must run them
    OUTSIDE the pool lock so submitters never queue behind disk I/O,
    while the fresh-beat-before-install ordering (no false ejection of
    the newcomer) still holds."""
    from pdnlp_tpu.parallel import watchdog

    r, _ = _router(n=2, start=False)
    r._lock = _OwnerLock()
    r._cond = threading.Condition(r._lock)
    r.start()
    assert r.wait_ready(10)
    violations = []
    real_beat = watchdog.Heartbeat.beat

    def checked_beat(self, *a, **kw):
        if r._lock.owner == threading.get_ident():
            violations.append("heartbeat write under the pool lock")
        return real_beat(self, *a, **kw)

    monkeypatch.setattr(watchdog.Heartbeat, "beat", checked_beat)
    try:
        r.kill_replica(1, "crash")
        deadline = time.monotonic() + 10
        while r.states[1] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.states[1] == "ejected"
        r.relaunch(1, engine=FakeEngine())
        assert r.wait_ready(10)
        assert violations == []
        assert r.states[1] in ("warming", "healthy")
    finally:
        r.stop(drain=False)


def test_knob_values_reads_under_the_pool_lock():
    """threadlint T1 regression: the knob snapshot synchronizes with
    apply_knob writers (a torn multi-knob read could hand the controller
    a tier ordering no actuation ever installed)."""
    r, _ = _router(n=1)
    try:
        got = {}
        acquired = threading.Event()
        release = threading.Event()

        def holder():
            with r._lock:
                acquired.set()
                release.wait(timeout=5)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert acquired.wait(timeout=5)
        t2 = threading.Thread(
            target=lambda: got.update(knobs=r.knob_values()), daemon=True)
        t2.start()
        t2.join(timeout=0.2)
        assert "knobs" not in got  # blocked behind the lock holder
        release.set()
        t2.join(timeout=5)
        t.join(timeout=5)
        assert got["knobs"]["max_wait_ms"] == r.max_wait_ms
    finally:
        r.stop(drain=False)
