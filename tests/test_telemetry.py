"""PR-10 telemetry plane: per-request distributed tracing (hop-chain
integrity under requeue/hedge/re-pack chaos), cross-rank trace merge with
clock alignment, the live Prometheus exporter + bounded flight recorder,
HBM accounting, and the crash-path telemetry flush."""
import json
import os
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pdnlp_tpu.obs.exporter import (  # noqa: E402
    MetricsExporter, prometheus_text,
)
from pdnlp_tpu.obs.memory import MemorySampler, memory_snapshot  # noqa: E402
from pdnlp_tpu.obs.merge import merge_traces  # noqa: E402
from pdnlp_tpu.obs.phases import StepBreakdown, format_table  # noqa: E402
from pdnlp_tpu.obs.regress import diff_breakdowns  # noqa: E402
from pdnlp_tpu.obs.request import (  # noqa: E402
    chain_issues, chains, hop_chain, mint_request_id, record_hop,
    validate_chains,
)
from pdnlp_tpu.obs.trace import Tracer  # noqa: E402
from pdnlp_tpu.parallel.watchdog import GangMonitor, Heartbeat  # noqa: E402
from pdnlp_tpu.serve import DynamicBatcher, ReplicaRouter  # noqa: E402

from tests.test_router import FakeEngine  # noqa: E402
from tests.test_serve_pack import FakePackEngine  # noqa: E402


# --------------------------------------------------------------- chain core

def test_request_ids_unique_and_monotonic():
    a, b = mint_request_id(), mint_request_id()
    assert a != b
    assert a.startswith(f"r{os.getpid()}-")
    assert int(a.rsplit("-", 1)[1]) < int(b.rsplit("-", 1)[1])


def test_chain_issues_contract():
    def rec(hop, t):
        return {"name": "hop", "t0": t, "dur": 0.0,
                "attrs": {"request_id": "r1-1", "hop": hop}}

    ok = [rec("admit", 1.0), rec("dispatch", 2.0), rec("complete", 3.0)]
    assert chain_issues(ok) == []
    assert chain_issues([]) == ["empty chain"]
    # orphaned: no terminal
    assert any("orphaned" in i
               for i in chain_issues(ok[:2]))
    # duplicate completion (a hedge/requeue double-complete bug)
    assert any("duplicate" in i
               for i in chain_issues(ok + [rec("complete", 5.0)]))
    # a requeue recorded past the terminal is an integrity violation...
    assert chain_issues([rec("admit", 1.0), rec("complete", 2.0),
                         rec("requeue", 3.0)])
    # ...but a trailing dispatch/pack is the hedge's LOSING copy marking
    # its (duplicate) execution — truthful telemetry, not a violation
    assert chain_issues([rec("admit", 1.0), rec("complete", 2.0),
                         rec("dispatch", 3.0)]) == []
    # a request refused at the door is a complete one-hop life
    assert chain_issues([rec("rejected", 1.0)]) == []
    assert chain_issues([rec("shed", 1.0)]) == []


def test_disabled_tracer_records_no_hops():
    tr = Tracer(enabled=False)
    record_hop(tr, "r1-1", "admit")
    assert tr.records() == []


# --------------------------------------------------- batcher + router chains

def test_batcher_end_to_end_chain():
    eng = FakeEngine()
    eng.tracer = Tracer(enabled=True)
    b = DynamicBatcher(eng, buckets=(32,), max_batch_size=2,
                       max_wait_ms=2.0)
    b.start()
    try:
        futs = [b.submit_ids([2, 3, 4]) for _ in range(4)]
        for f in futs:
            f.result(timeout=10)
    finally:
        b.stop()
    report = validate_chains(eng.tracer.records(),
                             [f.rid for f in futs])
    assert report == {"checked": 4, "complete": 4, "incomplete": {},
                      "requeued": 0, "repacked": 0, "hedged": 0,
                      "shadowed": 0, "degraded": 0, "rolled_back": 0,
                      "streamed": 0, "re_prefilled": 0, "handed_off": 0,
                      "speculated": 0, "accept_rate": None}
    chain = hop_chain(eng.tracer.records(), futs[0].rid)
    hops = [(r["attrs"]["hop"]) for r in chain]
    assert hops == ["admit", "dispatch", "complete"]
    assert chain[0]["attrs"]["bucket"] == 32  # queue placement rides admit


def _traced_router(n=2, engines=None, **kw):
    engines = engines or [FakeEngine() for _ in range(n)]
    kw.setdefault("buckets", (32, 64))
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_wait_ms", 5.0)
    kw.setdefault("stall_timeout", 0.5)
    kw.setdefault("poll_interval", 0.02)
    kw.setdefault("tracer", Tracer(enabled=True))
    r = ReplicaRouter(engines, **kw)
    r.start()
    assert r.wait_ready(10)
    return r, engines


def test_request_ids_survive_crash_requeue():
    """The chaos-integrity contract: a mid-storm replica kill requeues
    its requests onto survivors and every accepted ID still reconstructs
    ONE complete chain — no duplicate terminals, no orphans."""
    r, engines = _traced_router(n=2)
    try:
        futs = [r.submit_ids([2, 3, 4], deadline_ms=30_000)
                for _ in range(12)]
        r.kill_replica(0, "crash")
        for f in futs:
            f.result(timeout=30)
        report = validate_chains(r.tracer.records(),
                                 [f.rid for f in futs])
        assert report["incomplete"] == {}
        assert report["complete"] == 12
        # the kill stranded real work: some chain crossed the ejection
        assert report["requeued"] >= 1
        # a requeued chain shows the move replica->replica with one
        # terminal
        by_id = chains(r.tracer.records())
        moved = next(f.rid for f in futs
                     if any((h.get("attrs") or {}).get("hop") == "requeue"
                            for h in by_id[f.rid]))
        hops = [h["attrs"]["hop"] for h in by_id[moved]]
        assert hops[0] == "admit" and hops[-1] == "complete"
        assert hops.count("complete") == 1
        req = [h["attrs"] for h in by_id[moved]
               if h["attrs"]["hop"] == "requeue"][0]
        assert req["from_replica"] == 0 and req["to_replica"] == 1
    finally:
        r.stop(drain=False)


def test_hedge_first_wins_records_one_terminal():
    slow, fast = FakeEngine(latency=0.3), FakeEngine()
    r, _ = _traced_router(engines=[slow, fast], max_wait_ms=1.0,
                          hedge_ms=30.0, stall_timeout=5.0,
                          poll_interval=0.01)
    try:
        # pile work on replica 0 (slow) so the hedge scan finds replica 1
        # strictly less loaded
        futs = [r.submit_ids([2, 3], deadline_ms=20_000)
                for _ in range(6)]
        for f in futs:
            f.result(timeout=30)
        report = validate_chains(r.tracer.records(),
                                 [f.rid for f in futs])
        assert report["incomplete"] == {}
        assert r.metrics.hedges_total.value >= 1
        assert report["hedged"] >= 1  # and STILL exactly one terminal
    finally:
        r.stop(drain=False)


def test_packed_eject_repack_keeps_ids_joinable():
    """Eject-time re-pack: the victim's queued requests ride a survivor's
    packed batch under the SAME id — requeue hop carries packed=True and
    the chain completes once."""
    engines = [FakePackEngine() for _ in range(2)]
    r, _ = _traced_router(engines=engines, buckets=(32, 64, 128),
                          max_batch_size=4, max_wait_ms=1000.0,
                          serve_pack="on")
    try:
        # 6 x 4 tokens sit far below the 4x128-token flush budget, and
        # the 1s age bound outlives the kill->eject hop: everything is
        # still QUEUED (least-loaded spreads over both replicas) when
        # the kill lands
        reqs = [r.submit_ids([2, 5, 5, 3], deadline_ms=30_000)
                for _ in range(6)]
        r.kill_replica(1, "crash")
        for q in reqs:
            q.result(timeout=10)
        report = validate_chains(r.tracer.records(),
                                 [q.rid for q in reqs])
        assert report["incomplete"] == {}
        # replica 1's share (least-loaded alternation -> ~half) re-packed
        assert report["repacked"] >= 2
        by_id = chains(r.tracer.records())
        moved = next(q.rid for q in reqs
                     if any((h.get("attrs") or {}).get("hop") == "requeue"
                            for h in by_id[q.rid]))
        chain = by_id[moved]
        hops = [c["attrs"]["hop"] for c in chain]
        assert hops[-1] == "complete" and hops.count("complete") == 1
        req = [c["attrs"] for c in chain
               if c["attrs"]["hop"] == "requeue"][0]
        assert req["packed"] is True
        # pack placement (row, slot) recorded on the survivor
        pack = [c["attrs"] for c in chain
                if c["attrs"]["hop"] == "pack"][-1]
        assert pack["replica"] == 0
        assert "row" in pack and "slot" in pack
    finally:
        r.stop(drain=False)


def test_deadline_expiry_is_a_terminal_hop():
    eng = FakeEngine(latency=0.2)
    eng.tracer = Tracer(enabled=True)
    b = DynamicBatcher(eng, buckets=(32,), max_batch_size=8,
                       max_wait_ms=1.0)
    b.start()
    try:
        blocker = b.submit_ids([2, 3])
        time.sleep(0.05)  # the worker is now inside the 0.2s forward
        doomed = b.submit_ids([2, 3], deadline_ms=5.0)
        with pytest.raises(Exception):
            doomed.result(timeout=10)
        blocker.result(timeout=10)
    finally:
        b.stop(drain=False)
    chain = hop_chain(eng.tracer.records(), doomed.rid)
    assert chain_issues(chain) == []
    assert chain[-1]["attrs"]["hop"] == "deadline"


# ----------------------------------------------------------- cross-rank merge

def _rank_trace(tmp_path, rank, t_base, wall_offset, n_steps=8,
                step_ms=10.0):
    """One rank's flushed trace: n steps of device_block at step_ms, with
    a clock domain starting at t_base and wall = mono + wall_offset."""
    tr = Tracer(str(tmp_path), enabled=True, process_index=rank,
                clock=lambda: _rank_trace.now)
    _rank_trace.now = t_base
    for i in range(n_steps):
        with tr.span("device_block", step=i + 1, n=1):
            _rank_trace.now += step_ms / 1e3
        _rank_trace.now += 0.001
    # flush writes the _clock_sync record pairing tracer clock with wall
    import pdnlp_tpu.obs.trace as trace_mod
    real_time = trace_mod.time.time
    trace_mod.time.time = lambda: _rank_trace.now + wall_offset
    try:
        path = tr.flush()
    finally:
        trace_mod.time.time = real_time
    return path


def test_merge_aligns_clocks_and_is_monotonic(tmp_path):
    # rank 0 and rank 1 share wall time but have perf_counter zeros 1000s
    # apart; both wall offsets chosen so aligned spans INTERLEAVE
    p0 = _rank_trace(tmp_path, 0, t_base=5.0, wall_offset=100.0)
    p1 = _rank_trace(tmp_path / "r1", 1, t_base=1005.0,
                     wall_offset=-899.995)
    records, report = merge_traces([p0, p1])
    assert report["aligned"] and report["ranks"] == [0, 1]
    ts = [r["t0"] for r in records]
    assert ts == sorted(ts)  # monotonic merged timeline
    pids = {r["pid"] for r in records}
    assert pids == {0, 1}
    # the two ranks genuinely interleave after alignment (without it,
    # rank 1's spans would all sort 1000s later)
    order = [r["pid"] for r in records]
    assert order != sorted(order)


def test_merged_summary_per_rank_and_diff_matches_per_rank(tmp_path):
    p0 = _rank_trace(tmp_path, 0, t_base=0.0, wall_offset=50.0,
                     step_ms=10.0)
    p1 = _rank_trace(tmp_path / "r1", 1, t_base=500.0, wall_offset=-450.0,
                     step_ms=30.0)  # a 3x slower rank
    records, _ = merge_traces([p0, p1])
    summary = StepBreakdown.from_records(records).summary()
    assert summary["steps"] == 16
    by_rank = summary["by_rank"]
    assert set(by_rank) == {"0", "1"}
    m0 = by_rank["0"]["phases"]["device_block"]["mean_sec"]
    m1 = by_rank["1"]["phases"]["device_block"]["mean_sec"]
    assert m1 == pytest.approx(3 * m0, rel=0.05)  # the slow rank is
    assert "rank 1:" in format_table(summary)     # attributable as itself
    # diff over merged traces agrees with per-rank diff within the noise
    # floor: merged-vs-merged of the same records is a zero delta
    d = diff_breakdowns(summary, summary, threshold=0.05)
    assert d["regressions"] == []
    assert d["phases"]["device_block"]["delta_ratio"] == 0.0


def test_diff_on_merged_matches_per_rank_diff(tmp_path):
    """A uniform 1.5x slowdown on both ranks: the merged diff and each
    per-rank diff report the same delta within the noise floor, and all
    flag the regression."""
    base = [_rank_trace(tmp_path / "b0", 0, 0.0, 10.0, step_ms=10.0),
            _rank_trace(tmp_path / "b1", 1, 300.0, -290.0, step_ms=10.0)]
    cand = [_rank_trace(tmp_path / "c0", 0, 0.0, 10.0, step_ms=15.0),
            _rank_trace(tmp_path / "c1", 1, 300.0, -290.0, step_ms=15.0)]

    def summ(paths):
        records, _ = merge_traces(paths)
        return StepBreakdown.from_records(records).summary()

    merged = diff_breakdowns(summ(base), summ(cand), threshold=0.2)
    assert "device_block" in merged["regressions"]
    m_delta = merged["phases"]["device_block"]["delta_ratio"]
    for rank in (0, 1):
        per = diff_breakdowns(summ([base[rank]]), summ([cand[rank]]),
                              threshold=0.2)
        assert "device_block" in per["regressions"]
        assert per["phases"]["device_block"]["delta_ratio"] == \
            pytest.approx(m_delta, abs=0.02)  # the noise floor


def test_merge_heartbeat_fallback(tmp_path):
    """A trace with no _clock_sync record aligns through the rank's beat
    payload (wall t + mono pair)."""
    from pdnlp_tpu.obs.export import write_jsonl
    from pdnlp_tpu.obs.merge import _offset_from_heartbeat

    hb = Heartbeat(str(tmp_path), 3, interval=0.0)
    hb.beat(force=True, step=7)
    off = _offset_from_heartbeat(str(tmp_path), 3)
    assert off is not None
    # the pair was read back-to-back: offset ~= time() - perf_counter()
    assert off == pytest.approx(time.time() - time.perf_counter(),
                                abs=0.5)
    # a bare trace (no sync record) + hb_dir -> aligned via heartbeat
    path = os.path.join(str(tmp_path), "trace_proc3.jsonl")
    write_jsonl([{"name": "device_block", "t0": 1.0, "dur": 0.01,
                  "tid": 0, "depth": 0}], path, process_index=3)
    _, report = merge_traces([path], hb_dir=str(tmp_path))
    assert report["files"][0]["clock_source"] == "heartbeat"


# ------------------------------------------------------------- live exporter

def test_exporter_serves_metrics_and_healthz(tmp_path):
    flight = str(tmp_path / "flight.jsonl")
    snap = {"requests_total": 7, "supported": True,
            "replicas": {"0": {"queue_depth": 2}, "1": {"queue_depth": 3}}}
    ex = MetricsExporter({"serve": lambda: snap}, port=0,
                         flight_path=flight,
                         flight_interval_s=0.05).start()
    try:
        time.sleep(0.15)
        base = f"http://127.0.0.1:{ex.port}"
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        hz = json.loads(urllib.request.urlopen(base + "/healthz",
                                               timeout=5).read())
    finally:
        ex.stop()
    assert "pdnlp_serve_requests_total 7" in body
    assert "pdnlp_serve_supported 1" in body  # bools export as 0/1
    assert 'pdnlp_serve_replicas_queue_depth{replica="1"} 3' in body
    assert hz["status"] == "ok" and "serve" in hz["sources"]
    # the flight recorder appended at its cadence AND on stop
    lines = [json.loads(x) for x in open(flight)]
    assert len(lines) >= 2
    assert lines[-1]["serve"]["requests_total"] == 7


def test_exporter_flight_recorder_is_bounded(tmp_path):
    flight = str(tmp_path / "flight.jsonl")
    ex = MetricsExporter({"s": lambda: {"v": 1}}, port=None,
                         flight_path=flight, flight_max_records=10)
    ex.start()
    try:
        for _ in range(40):
            ex._flight_append()
    finally:
        ex.stop(final_flight=False)
    n = sum(1 for _ in open(flight))
    assert n <= 10  # truncated to the newest half past the bound


def test_exporter_sick_source_does_not_blind_the_rest():
    def boom():
        raise RuntimeError("sick")

    ex = MetricsExporter({"bad": boom, "good": lambda: {"v": 3}},
                         port=None)
    snaps = ex.collect()
    assert snaps["good"] == {"v": 3}
    assert "RuntimeError" in snaps["bad"]["error"]
    assert "pdnlp_good_v 3" in prometheus_text(snaps)


def test_decode_snapshot_carries_the_round_account_and_it_scrapes():
    """``DecodeBatcher.snapshot()["rounds"]`` — the worker's own account of
    its rounds, kept with no profiler and no ``--trace`` — is JSON-ready and
    flattens into gauges through the exporter that is there."""
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.serve import DecodeBatcher, PagedDecodeEngine
    from pdnlp_tpu.utils.config import Args

    tok = WordPieceTokenizer(build_vocab(["天地人你我"], size=64))
    args = Args(model="bert-tiny", decode_slots=2, decode_max_len=32,
                max_new_tokens=4, kv_page_sz=16)
    eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None, buckets=(16,),
                            prefill_rows=2, tracer=Tracer(enabled=False))
    with DecodeBatcher(eng, replica=3) as b:
        b.eos_id = -1
        for s in [b.submit_ids([5, 6, 7 + i], max_new_tokens=4)
                  for i in range(3)]:
            s.result(timeout=120)
    snap = b.snapshot()     # stopped: the last round's row is written
    assert eng.tracer.records() == []          # nothing was traced
    acc = json.loads(json.dumps(snap))["rounds"]
    assert acc["rounds"] == len(eng.tracer.rounds()) >= 4
    assert acc["builds"] >= 1                  # unwarmed: it compiled
    assert set(acc["kinds"]) >= {"prefill", "decode"}
    assert acc["longest"][0]["replica"] == 3
    assert snap["engine"]["compile_cache"]["executables_built"] >= 1
    text = prometheus_text({"decode": snap})
    assert f'pdnlp_decode_rounds_rounds {acc["rounds"]}' in text
    assert "pdnlp_decode_rounds_kinds_decode_wall_ms_p50 " in text
    assert "pdnlp_decode_rounds_kinds_decode_parts_other_mean_ms " in text
    # the worker's CPU clock is read every tenth of a second: the compiles
    # of this unwarmed engine alone take longer
    assert "pdnlp_decode_rounds_cpu_host_off_cpu_ms_per_round " in text
    assert acc["window_sec"] == 30.0            # the last 30 s of rounds
    assert 'pdnlp_decode_rounds_longest_wall_ms{longest="0"} ' in text
    assert "pdnlp_decode_engine_compile_cache_executables_built " in text


# ------------------------------------------------------------ HBM accounting

class _FakeDevice:
    def __init__(self, i, in_use, peak, limit=16 << 30):
        self.id = i
        self._s = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                   "bytes_limit": limit}

    def memory_stats(self):
        return dict(self._s)


def test_memory_sampler_unsupported_is_noop():
    # CPU devices report no memory_stats: first sample flips supported
    sampler = MemorySampler()
    assert sampler.sample() is None or sampler.supported  # TPU hosts pass
    if not sampler.supported:
        assert sampler.snapshot() == {"supported": False}
        assert sampler.beat_payload() == {}
        assert memory_snapshot() == {"supported": False}


def test_memory_sampler_tracks_phase_peaks_and_feeds_trace():
    tr = Tracer(enabled=True)
    devs = [_FakeDevice(0, 1 << 30, 2 << 30), _FakeDevice(1, 1 << 30,
                                                          3 << 30)]
    sampler = MemorySampler(devices=devs, tracer=tr)
    tr.add_listener(sampler.feed)
    with tr.span("device_block", step=1, n=1):
        pass
    devs[0]._s["peak_bytes_in_use"] = 5 << 30
    with tr.span("eval", step=1):
        pass
    snap = sampler.snapshot(sample=False)
    assert snap["supported"]
    assert snap["peak_bytes_in_use"] == 8 << 30  # 5 + 3 GiB summed peaks
    assert snap["device_peak_bytes"] == 5 << 30
    assert set(snap["per_phase"]) == {"device_block", "eval"}
    assert sampler.beat_payload()["hbm_peak"] == 8 << 30
    # samples landed in the trace as "hbm" records -> breakdown memory row
    bd = StepBreakdown.from_records(tr.records())
    s = bd.summary()
    assert s["memory"]["peak_bytes"] == 8 << 30
    assert "peak HBM" in format_table(s)


def test_serve_tables_carry_replica_hbm_column():
    bd = StepBreakdown()
    bd.feed({"name": "forward", "t0": 0.0, "dur": 0.01, "tid": 0,
             "depth": 0, "attrs": {"replica": 0, "fill": 0.9,
                                   "hbm_peak": 4 << 30}})
    s = bd.summary()
    assert s["serve_by_replica"]["0"]["hbm_peak_gb"] == 4.0
    assert "peak HBM 4.000 GB" in format_table(s)


def test_gang_status_line_reports_peak_hbm(tmp_path):
    hb0 = Heartbeat(str(tmp_path), 0, interval=0.0)
    hb1 = Heartbeat(str(tmp_path), 1, interval=0.0)
    hb0.beat(force=True, step=5, hbm=1 << 30, hbm_peak=2 << 30)
    hb1.beat(force=True, step=4, hbm=1 << 30, hbm_peak=6 << 30)

    class _P:
        def poll(self):
            return None

    mon = GangMonitor([_P(), _P()], str(tmp_path), 2, stall_timeout=60.0)
    mon.started = 0.0  # beats above predate monitor construction
    s = mon.status()
    assert s["last_step"] == 4            # the laggard's step
    assert s["hbm_peak_gb"] == 6.0        # the hottest rank's peak
    assert "peak HBM 6.0 GB" in mon.status_line()


# ------------------------------------------------------- crash-path flush

def test_eject_flushes_spans_and_snapshot_to_disk(tmp_path):
    """The satellite regression test: eject a replica and assert its
    spans AND a final metrics snapshot are on disk — no clean exit
    required."""
    trace_dir = str(tmp_path / "trace")
    tele_dir = str(tmp_path / "tele")
    os.makedirs(tele_dir)
    tracer = Tracer(trace_dir, enabled=True, process_index=0)
    r, engines = _traced_router(n=2, tracer=tracer,
                                telemetry_dir=tele_dir)
    try:
        futs = [r.submit_ids([2, 3, 4], deadline_ms=30_000)
                for _ in range(8)]
        for f in futs:  # the victim served real batches before dying
            f.result(timeout=20)
        r.kill_replica(0, "crash")
        deadline = time.monotonic() + 10
        while r.states[0] != "ejected" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r.states[0] == "ejected"
        snap_path = os.path.join(tele_dir, "router_snapshot.json")
        trace_path = os.path.join(trace_dir, "trace_proc0.jsonl")
        # the state flips at the TOP of _eject's locked block; the flush
        # runs after the requeue work, outside the lock — poll briefly
        # instead of racing the file write (the contract is "on disk
        # without a clean exit", not "on disk the same microsecond")
        deadline = time.monotonic() + 10
        while not (os.path.exists(snap_path) and os.path.exists(trace_path)) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(snap_path), "eject left no metrics snapshot"
        assert os.path.exists(trace_path), "eject left no span file"
        snap = json.load(open(snap_path))
        assert snap["router"]["ejections_total"] == 1
        assert snap["event"].startswith("eject replica 0")
        # the condemned replica's batches are in the flushed spans
        from pdnlp_tpu.obs.export import load_records

        recs = load_records(trace_path)
        assert any((r_.get("attrs") or {}).get("replica") == 0
                   for r_ in recs if r_.get("name") == "queue_wait")
    finally:
        r.stop(drain=False)


# ------------------------------------------------------------- trace_tpu CLI

def test_trace_tpu_request_and_merge_cli(tmp_path, capsys):
    sys.path.insert(0, REPO)
    import trace_tpu

    eng = FakeEngine()
    eng.tracer = Tracer(str(tmp_path), enabled=True, process_index=0)
    b = DynamicBatcher(eng, buckets=(32,), max_batch_size=2,
                       max_wait_ms=1.0)
    b.start()
    try:
        futs = [b.submit_ids([2, 3, 4]) for _ in range(2)]
        for f in futs:
            f.result(timeout=10)
    finally:
        b.stop()
    path = eng.tracer.flush()

    assert trace_tpu.main(["request", futs[0].rid, path]) == 0
    out = capsys.readouterr().out
    assert "admit" in out and "complete" in out and "chain: complete" in out
    # unknown id -> exit 1
    assert trace_tpu.main(["request", "r0-999999", path]) == 1
    capsys.readouterr()

    merged = str(tmp_path / "merged.trace.json")
    assert trace_tpu.main(["merge", path, "-o", merged]) == 0
    doc = json.load(open(merged))
    assert doc["traceEvents"]
    # summarize accepts the merged chrome export
    assert trace_tpu.main(["summarize", merged]) == 0
