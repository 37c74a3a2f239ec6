"""pdnlp_tpu.obs: span recording, phase breakdown, exporters, the
regression detector, and the trace_tpu.py CLI.

The dispatch-vs-block attribution test runs a real jitted fn; everything
else is pure-host (synthetic records through the same code paths the
trainer feeds), so the math assertions are exact, not timing-dependent.
"""
import json
import os
import time

import pytest

import trace_tpu
from pdnlp_tpu.obs import (
    PHASES, RegressionDetector, StepBreakdown, Tracer, diff_breakdowns,
    format_table,
)
from pdnlp_tpu.obs.export import (
    from_chrome_trace, load_records, to_chrome_trace, write_chrome_trace,
    write_jsonl,
)


# --------------------------------------------------------------- tracer core

def test_span_records_name_duration_and_attrs():
    t = {"now": 0.0}
    tr = Tracer(enabled=True, clock=lambda: t["now"])
    with tr.span("step_dispatch", step=7, n=2):
        t["now"] += 0.25
    (rec,) = tr.records()
    assert rec["name"] == "step_dispatch"
    assert rec["dur"] == pytest.approx(0.25)
    assert rec["attrs"] == {"step": 7, "n": 2}
    assert rec["depth"] == 0


def test_span_nesting_tracks_depth_and_set_updates_attrs():
    tr = Tracer(enabled=True)
    with tr.span("outer") as outer:
        with tr.span("inner"):
            pass
        outer.set(bytes=128)
    inner, outer = tr.records()
    assert (inner["name"], inner["depth"]) == ("inner", 1)
    assert (outer["name"], outer["depth"]) == ("outer", 0)
    assert outer["attrs"] == {"bytes": 128}
    # inner closed before outer: the record stream is completion-ordered
    assert inner["t0"] >= outer["t0"]


def test_disabled_tracer_records_nothing_and_shares_one_null_span():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is s2  # zero allocation per use
    with s1:
        pass
    assert tr.block(object()) is not None  # passthrough, no barrier
    assert tr.records() == []
    assert tr.flush() is None


def test_wrap_iter_times_each_next_and_preserves_items():
    tr = Tracer(enabled=True)
    out = list(tr.wrap_iter("data_wait", iter([1, 2, 3])))
    assert out == [1, 2, 3]
    recs = tr.records()
    # one span per next() INCLUDING the final StopIteration probe
    assert [r["name"] for r in recs] == ["data_wait"] * 4


def test_record_explicit_timestamps():
    tr = Tracer(enabled=True)
    tr.record("queue_wait", 10.0, 10.5, bucket=64)
    (rec,) = tr.records()
    assert rec["dur"] == pytest.approx(0.5)
    assert rec["attrs"] == {"bucket": 64}


def test_ring_buffer_caps_history():
    tr = Tracer(enabled=True, capacity=8)
    for i in range(20):
        with tr.span("log", i=i):
            pass
    recs = tr.records()
    assert len(recs) == 8
    assert recs[-1]["attrs"]["i"] == 19  # most recent window kept


def test_listener_sees_every_record_and_can_be_removed():
    tr = Tracer(enabled=True)
    seen = []
    tr.add_listener(seen.append)
    with tr.span("eval"):
        pass
    tr.remove_listener(seen.append)
    with tr.span("eval"):
        pass
    assert len(seen) == 1 and seen[0]["name"] == "eval"


# ------------------------------------------------- dispatch/block attribution

def test_jitted_fn_dispatch_and_block_are_separate_spans():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    f(x).block_until_ready()  # compile outside the traced window

    tr = Tracer(enabled=True)
    with tr.span("step_dispatch", step=1, n=1):
        y = f(x)
    out = tr.block(y, step=1, n=1)
    assert out is y  # block returns its input materialized
    dispatch, block = tr.records()
    assert dispatch["name"] == "step_dispatch"
    assert block["name"] == "device_block"
    assert block["attrs"] == {"step": 1, "n": 1}
    # the block span OPENS after the dispatch span closed: device time is
    # never smeared into the dispatch measurement
    assert block["t0"] >= dispatch["t0"] + dispatch["dur"]


def test_span_block_records_child_device_block():
    import jax.numpy as jnp

    tr = Tracer(enabled=True)
    with tr.span("step_dispatch") as sp:
        sp.block(jnp.ones(4))
    block, dispatch = tr.records()
    assert (block["name"], block["depth"]) == ("device_block", 1)
    assert (dispatch["name"], dispatch["depth"]) == ("step_dispatch", 0)


# ----------------------------------------------------------- breakdown math

def _rec(name, dur, **attrs):
    r = {"name": name, "t0": 0.0, "dur": dur, "tid": 0, "depth": 0}
    if attrs:
        r["attrs"] = attrs
    return r


def test_breakdown_aggregates_phases_per_step():
    bd = StepBreakdown()
    for step in (1, 2):
        bd.feed(_rec("data_wait", 0.010))
        bd.feed(_rec("h2d_put", 0.002))
        bd.feed(_rec("h2d_put", 0.001))     # several spans, one step total
        bd.feed(_rec("step_dispatch", 0.001))
        bd.feed(_rec("device_block", 0.100, step=step))
    bd.feed(_rec("not_a_phase", 9.9))        # foreign vocabulary: ignored
    bd.close()
    s = bd.summary()
    assert s["steps"] == 2 and s["groups"] == 2
    assert set(s["phases"]) == {"data_wait", "h2d_put", "step_dispatch",
                                "device_block"}
    put = s["phases"]["h2d_put"]
    assert put["count"] == 2
    assert put["total_sec"] == pytest.approx(0.006)
    assert put["mean_sec"] == pytest.approx(0.003)
    # shares sum to 1 over the traced wall time
    assert sum(p["share"] for p in s["phases"].values()) \
        == pytest.approx(1.0, abs=1e-3)


def test_breakdown_fused_groups_count_n_steps():
    bd = StepBreakdown()
    bd.feed(_rec("step_dispatch", 0.004))
    bd.feed(_rec("device_block", 0.050, step=4, n=4))
    bd.close()
    s = bd.summary()
    assert s["steps"] == 4 and s["groups"] == 1


def test_breakdown_percentiles():
    bd = StepBreakdown()
    for ms in range(1, 101):  # 1..100 ms, one per step
        bd.record("data_wait", ms / 1e3)
        bd.end_step()
    s = bd.summary()["phases"]["data_wait"]
    assert s["p50_sec"] == pytest.approx(0.0505)
    assert s["p95_sec"] == pytest.approx(0.09505)
    assert s["mean_sec"] == pytest.approx(0.0505)


def test_breakdown_counts_nested_phase_spans_once():
    """Sync mode's shape: the h2d_put span runs INSIDE the data_wait span
    (the upload happens in the generator, under wrap_iter's next).  Each
    second must land in exactly one phase — data_wait reports its SELF
    time, not wait + upload double-counted."""
    t = {"now": 0.0}
    tr = Tracer(enabled=True, clock=lambda: t["now"])
    bd = StepBreakdown()
    tr.add_listener(bd.feed)
    with tr.span("data_wait"):
        t["now"] += 0.002          # collation before the upload
        with tr.span("h2d_put"):
            t["now"] += 0.010      # the upload itself
        t["now"] += 0.001          # collation after
    with tr.span("device_block"):
        t["now"] += 0.050
    bd.close()
    s = bd.summary()["phases"]
    assert s["h2d_put"]["total_sec"] == pytest.approx(0.010)
    assert s["data_wait"]["total_sec"] == pytest.approx(0.003)  # self time
    assert s["device_block"]["total_sec"] == pytest.approx(0.050)


def test_breakdown_feed_is_thread_safe():
    """The prefetch worker records h2d_put on its own thread while the
    main thread closes steps: no seconds lost under interleaving."""
    import threading as th

    bd = StepBreakdown()
    N = 400

    def worker():
        for _ in range(N):
            bd.feed(_rec("h2d_put", 0.001))

    t = th.Thread(target=worker)
    t.start()
    for _ in range(N):
        bd.feed(_rec("step_dispatch", 0.001))
        bd.feed(_rec("device_block", 0.001))
    t.join()
    bd.close()
    s = bd.summary()["phases"]
    assert s["h2d_put"]["count"] and sum(
        (p["total_sec"] for p in s.values())) == pytest.approx(N * 3e-3)


def test_breakdown_on_step_fires_with_phase_dict():
    steps = []
    bd = StepBreakdown(on_step=lambda step, phases, wall:
                       steps.append((step, dict(phases), wall)))
    bd.feed(_rec("data_wait", 0.2))
    bd.feed(_rec("device_block", 0.3, step=17))
    (step, phases, wall), = steps
    assert step == 17
    assert phases == {"data_wait": 0.2, "device_block": 0.3}
    assert wall == pytest.approx(0.5)


def test_format_table_lists_every_phase():
    bd = StepBreakdown()
    bd.feed(_rec("data_wait", 0.2))
    bd.feed(_rec("device_block", 0.3))
    bd.close()
    table = format_table(bd.summary())
    assert "data_wait" in table and "device_block" in table
    assert "steps: 1" in table


# -------------------------------------------------------------- export schema

def test_chrome_trace_required_keys_and_units(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("step_dispatch", step=1):
        time.sleep(0.001)
    doc = to_chrome_trace(tr.records(), process_index=3)
    assert "traceEvents" in doc and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        for key in ("name", "ph", "ts", "pid", "tid"):  # schema-required
            assert key in ev, f"missing {key}"
        assert ev["ph"] == "X"
        assert ev["pid"] == 3
        assert ev["dur"] >= 1000  # microseconds: the 1ms sleep is >= 1000us
    path = str(tmp_path / "t.json")
    write_chrome_trace(tr.records(), path)
    assert json.load(open(path))["traceEvents"]


def test_jsonl_roundtrip_and_chrome_roundtrip(tmp_path):
    recs = [_rec("data_wait", 0.01), _rec("device_block", 0.09, step=1)]
    jl = str(tmp_path / "trace_proc0.jsonl")
    write_jsonl(recs, jl, process_index=2)
    back = load_records(jl)
    assert [r["name"] for r in back] == ["data_wait", "device_block"]
    assert all(r["pid"] == 2 for r in back)
    # chrome roundtrip preserves names/durations/attrs
    doc = to_chrome_trace(recs)
    back2 = from_chrome_trace(doc)
    assert back2[1]["attrs"] == {"step": 1}
    assert back2[1]["dur"] == pytest.approx(0.09)
    # load_records sniffs an exported chrome file too
    cj = str(tmp_path / "t.json")
    write_chrome_trace(recs, cj)
    assert [r["name"] for r in load_records(cj)] == \
        ["data_wait", "device_block"]


def test_tracer_flush_writes_per_process_jsonl(tmp_path):
    tr = Tracer(str(tmp_path), enabled=True, process_index=1)
    with tr.span("eval"):
        pass
    path = tr.flush()
    assert path.endswith("trace_proc1.jsonl")
    assert load_records(path)[0]["name"] == "eval"
    assert tr.records()  # flush is a snapshot, not a drain


# ------------------------------------------------------- regression detector

def _observe_steps(det, n, phases, start=1):
    for i in range(n):
        det.observe(start + i, dict(phases), sum(phases.values()))


def test_regress_flags_sustained_slowdown_once():
    det = RegressionDetector(warmup=3, sustain=3, slow_ratio=1.3)
    _observe_steps(det, 10, {"data_wait": 0.010})
    assert det.events == []
    _observe_steps(det, 10, {"data_wait": 0.020}, start=11)  # 2x baseline
    kinds = [e["kind"] for e in det.events]
    assert kinds.count("slowdown") == 1  # one event per sustained run
    ev = det.events[0]
    assert ev["phase"] == "data_wait" and ev["ratio"] >= 1.3
    assert ev["sustained_steps"] >= 3


def test_regress_flags_one_off_stall_without_poisoning_baseline():
    det = RegressionDetector(warmup=3, sustain=3, spike_ratio=3.0)
    _observe_steps(det, 10, {"device_block": 0.100})
    det.observe(11, {"device_block": 1.0}, 1.0)  # 10x: GC-pause shape
    (ev,) = det.events
    assert ev["kind"] == "stall" and ev["ratio"] >= 3.0
    # the spike did not enter the EWMA: the next normal step is quiet
    det.observe(12, {"device_block": 0.100}, 0.1)
    assert len(det.events) == 1


def test_regress_quiet_on_steady_phases():
    det = RegressionDetector(warmup=3, sustain=3)
    _observe_steps(det, 50, {"data_wait": 0.010, "device_block": 0.100})
    assert det.events == []


def test_heartbeat_payload_carries_step_and_smoothed_rate():
    det = RegressionDetector()
    assert det.heartbeat_payload() == {}
    for i in range(1, 6):
        det.observe(i, {"device_block": 0.5}, 0.5)
    p = det.heartbeat_payload()
    assert p["step"] == 5
    assert p["steps_per_sec"] == pytest.approx(2.0, abs=0.01)


def test_diff_breakdowns_flags_only_above_threshold_and_noise_floor():
    def summary(mean):
        return {"phases": {"data_wait": {"mean_sec": mean, "count": 30},
                           "log": {"mean_sec": 1e-9, "count": 30}}}

    d = diff_breakdowns(summary(0.010), {"phases": {
        "data_wait": {"mean_sec": 0.013, "count": 30},  # +30%: flagged
        "log": {"mean_sec": 1e-7, "count": 30}}},  # 100x, under the floor
        threshold=0.2)
    assert d["regressions"] == ["data_wait"]
    assert d["phases"]["log"]["regressed"] is False
    d2 = diff_breakdowns(summary(0.010), summary(0.011), threshold=0.2)
    assert d2["regressions"] == []  # +10% is under threshold


def test_diff_breakdowns_min_count_guards_amortized_phases():
    """The resident pipeline's amortized h2d_put appears 1-2 times per
    run; its sub-ms mean swings wildly between identical configs — too
    few observations must never fail the gate."""
    base = {"phases": {"h2d_put": {"mean_sec": 0.0008, "count": 2}}}
    cand = {"phases": {"h2d_put": {"mean_sec": 0.0016, "count": 2}}}
    assert diff_breakdowns(base, cand)["regressions"] == []  # +100%, n=2
    # the same delta with enough observations IS a regression
    base["phases"]["h2d_put"]["count"] = 50
    cand["phases"]["h2d_put"]["count"] = 50
    assert diff_breakdowns(base, cand)["regressions"] == ["h2d_put"]


def test_diff_breakdowns_ckpt_save_budget_gate():
    """The async-checkpointing contract as a trace gate: the CANDIDATE's
    in-loop ckpt_save p95 is bounded ABSOLUTELY (independent of the base
    trace — a regression vs an already-bloated base must still fail)."""
    base = {"phases": {}}
    cand = {"phases": {"ckpt_save": {"mean_sec": 0.004, "p95_sec": 0.009,
                                     "count": 12}}}
    ok = diff_breakdowns(base, cand, ckpt_save_budget=0.010)
    assert ok["ckpt_save_budget"] == {"budget_sec": 0.010,
                                     "cand_p95_sec": 0.009,
                                     "exceeded": False}
    assert ok["regressions"] == []
    bad = diff_breakdowns(base, cand, ckpt_save_budget=0.005)
    assert bad["ckpt_save_budget"]["exceeded"] is True
    assert "ckpt_save(p95-budget)" in bad["regressions"]
    # a trace with no saves passes vacuously (nothing to measure)
    empty = diff_breakdowns(base, {"phases": {}}, ckpt_save_budget=0.005)
    assert empty["ckpt_save_budget"]["exceeded"] is False
    # the end-of-run drain (ckpt_wait) is NEVER the gated phase
    drained = diff_breakdowns(base, {"phases": {
        "ckpt_wait": {"mean_sec": 2.0, "p95_sec": 2.0, "count": 1}}},
        ckpt_save_budget=0.005)
    assert drained["regressions"] == []


def test_trace_diff_cli_ckpt_save_budget_exit_code(tmp_path):
    """End-to-end through trace_tpu.py diff: a trace whose in-loop
    ckpt_save p95 busts the budget exits 1; a generous budget exits 0."""
    import subprocess
    import sys

    from pdnlp_tpu.obs.export import write_jsonl

    def trace(path, save_sec):
        recs = []
        t = 0.0
        for i in range(1, 8):
            recs.append({"name": "step_dispatch", "t0": t, "dur": 0.001,
                         "tid": 0, "depth": 0})
            recs.append({"name": "ckpt_save", "t0": t + 0.001,
                         "dur": save_sec, "tid": 0, "depth": 0})
            recs.append({"name": "device_block", "t0": t + 0.002,
                         "dur": 0.01, "tid": 0, "depth": 0,
                         "attrs": {"step": i}})
            t += 0.02
        write_jsonl(recs, str(path), process_index=0)

    base, cand = tmp_path / "base.jsonl", tmp_path / "cand.jsonl"
    trace(base, 0.002)
    trace(cand, 0.002)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(budget):
        return subprocess.run(
            [sys.executable, os.path.join(repo, "trace_tpu.py"), "diff",
             str(base), str(cand), "--ckpt_save_budget", str(budget)],
            capture_output=True, text=True, env={**os.environ,
                                                 "PYTHONPATH": repo})

    assert run(0.010).returncode == 0
    over = run(0.001)
    assert over.returncode == 1
    assert "OVER BUDGET" in over.stdout


# ----------------------------------------------------------------- CLI paths

def _write_trace(tmp_path, name, block_ms):
    recs = []
    for step in range(1, 9):
        recs.append(_rec("data_wait", 0.002))
        recs.append(_rec("device_block", block_ms / 1e3, step=step))
    path = str(tmp_path / name)
    write_jsonl(recs, path)
    return path


def test_cli_diff_exits_nonzero_on_regression(tmp_path, capsys):
    base = _write_trace(tmp_path, "base.jsonl", block_ms=100)
    bad = _write_trace(tmp_path, "bad.jsonl", block_ms=125)  # +25% >= 20%
    assert trace_tpu.main(["diff", base, bad, "--threshold", "0.2"]) == 1
    assert "device_block" in capsys.readouterr().err
    # within threshold: clean exit
    ok = _write_trace(tmp_path, "ok.jsonl", block_ms=105)
    assert trace_tpu.main(["diff", base, ok, "--threshold", "0.2"]) == 0


def test_cli_summarize_and_export(tmp_path, capsys):
    trace = _write_trace(tmp_path, "t.jsonl", block_ms=50)
    assert trace_tpu.main(["summarize", trace]) == 0
    out = capsys.readouterr().out
    assert "device_block" in out and "steps: 8" in out
    assert trace_tpu.main(["summarize", trace, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["steps"] == 8
    chrome = str(tmp_path / "t.chrome.json")
    assert trace_tpu.main(["export", trace, "-o", chrome]) == 0
    doc = json.load(open(chrome))
    for ev in doc["traceEvents"]:
        assert all(k in ev for k in ("name", "ph", "ts", "pid", "tid"))


# ------------------------------------------------------ trainer integration

def test_traced_trainer_end_to_end(tmp_path, capsys):
    """A traced Trainer.train(): the step loop's spans fold into a phase
    breakdown (exposed as trainer.trace_summary), the span file flushes,
    and the end-of-train table prints."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from pdnlp_tpu.models import bert, get_config
    from pdnlp_tpu.train import (
        Trainer, build_optimizer, init_state, make_eval_step,
        make_train_step,
    )
    from pdnlp_tpu.utils.config import Args

    args = Args(model="bert-tiny", output_dir=str(tmp_path), epochs=2,
                dev=True, eval_step=4, log_every=2, train_batch_size=8,
                dev_batch_size=8, trace=True)
    cfg = get_config("bert-tiny", vocab_size=64, num_labels=6)
    params = bert.init_params(jax.random.key(0), cfg)
    tx = build_optimizer(params, args)
    state = init_state(jax.random.key(0), cfg, tx, rng=jax.random.key(1))

    class _ListLoader:
        def __init__(self, batches):
            self.batches = batches

        def __len__(self):
            return len(self.batches)

        def set_epoch(self, e):
            pass

        def __iter__(self):
            return iter(self.batches)

    rng = np.random.RandomState(0)
    ids = rng.randint(5, 64, (4, 8, 16)).astype(np.int32)
    batches = [{
        "input_ids": jnp.asarray(ids[i]),
        "token_type_ids": jnp.zeros((8, 16), jnp.int32),
        "attention_mask": jnp.ones((8, 16), jnp.int32),
        "label": jnp.asarray((ids[i][:, 1] % 6).astype(np.int32)),
        "example_weight": jnp.ones((8,), jnp.float32),
    } for i in range(4)]

    tracer = Tracer(str(tmp_path), enabled=True)
    trainer = Trainer(args, cfg, state, make_train_step(cfg, tx, args),
                      make_eval_step(cfg, args), tracer=tracer)
    trainer.train(_ListLoader(batches), _ListLoader(batches[:1]))

    s = trainer.trace_summary
    assert s is not None and s["steps"] == 8
    for phase in ("data_wait", "step_dispatch", "device_block", "eval"):
        assert phase in s["phases"], s["phases"].keys()
    assert s["phases"]["device_block"]["count"] == 8
    recs = load_records(tracer.trace_path())
    assert any(r["name"] == "device_block" for r in recs)
    out = capsys.readouterr().out
    assert "[obs] phase breakdown" in out and "device_block" in out
    # the listener was detached: spans after train() stay out of breakdowns
    assert trainer.trace_summary["steps"] == 8
    assert tracer._listeners == []

    # a run that RAISES must also detach (else the next traced train in
    # this process double-feeds every span into a stale breakdown)
    class _BoomLoader(_ListLoader):
        def __iter__(self):
            raise RuntimeError("boom")

    # (a fresh state: the first run's step donated the one above)
    state2 = init_state(jax.random.key(0), cfg, tx, rng=jax.random.key(1))
    t2 = Trainer(args, cfg, state2, make_train_step(cfg, tx, args),
                 make_eval_step(cfg, args), tracer=tracer)
    with pytest.raises(RuntimeError, match="boom"):
        t2.train(_BoomLoader(batches), None)
    assert tracer._listeners == []


# ------------------------------------------------------------ overhead smoke

def test_tracing_overhead_smoke():
    """Traced vs untraced host loop, best-of-5: an enabled span must cost
    microseconds, not milliseconds.  The loose 2x bound (against a ~30us
    workload) keeps this deterministic under CI contention; what tracing costs a real
    step is a chip measurement (PERF.md section 6)."""
    off = Tracer(enabled=False)
    on = Tracer(enabled=True, capacity=10_000)

    def loop(tr, n=500):
        t0 = time.perf_counter()
        acc = 0
        for i in range(n):
            with tr.span("step_dispatch", step=i):
                acc += sum(range(5000))  # ~50us: dominates the span cost
            tr.block(None)  # None: no jax import in the hot smoke
        return time.perf_counter() - t0, acc

    base = min(loop(off)[0] for _ in range(5))
    traced = min(loop(on)[0] for _ in range(5))
    assert traced < base * 2.0, (traced, base)


def test_phase_vocabulary_is_the_documented_eight():
    assert PHASES == ("data_wait", "h2d_put", "step_dispatch",
                      "device_block", "eval", "ckpt_save", "ckpt_wait",
                      "log")
