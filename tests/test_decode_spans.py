"""The decode worker's host phases as LEAF spans (``Tracer.leaf``): on the
tracer's ring buffer and, as ``bench:<name>`` annotations, on the JAX
profiler's host plane — switched on by a profiler session alone, without
``Args.trace``.  A bert-tiny paged engine behind a ``DecodeBatcher`` on the
CPU, under a REAL ``jax.profiler.start_trace``.

Pinned here: the leaves cover the worker's round and never nest; one
``request`` record per finished stream, joinable to its dispatch leaf by
``rid``; what a session does NOT switch on (the per-token hop stream, the
memory sample); that off is off (the shared no-op span, by identity); that
a submitter never waits on the worker's device wait; the engine-call phase
tables derived from the leaves; and ``trace_tpu.py summarize``'s
host-phase table on a recorded file."""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.obs import trace as obs_trace
from pdnlp_tpu.obs.phases import (
    CALL_LEAVES, StepBreakdown, decode_host_phases, format_decode_table,
    worker_leaf,
)
from pdnlp_tpu.obs.trace import _NULL_SPAN, ANNOTATION_PREFIX, Tracer
from pdnlp_tpu.serve import DecodeBatcher, PagedDecodeEngine
from pdnlp_tpu.serve import decode as decode_mod
from pdnlp_tpu.utils.config import Args

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "tests", "assets", "decode_worker_trace.jsonl")
TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
SHARED = list(range(5, 21))            # one full page every prompt shares
LEAF_NAMES = ("admit", "prefill.dispatch", "prefill.device_wait",
              "prefill.fetch", "prefill.emit", "chunk.dispatch",
              "chunk.device_wait", "chunk.fetch", "chunk.emit",
              "cow.dispatch", "decode.dispatch", "decode.device_wait",
              "decode.fetch", "decode.emit")


@pytest.fixture(scope="module")
def engine():
    """ONE warmed paged engine; ``Args.trace`` is false, so its tracer is
    the process-global one, disabled."""
    tok = WordPieceTokenizer(build_vocab(TEXTS, size=128))
    args = Args(model="bert-tiny", decode_slots=4, decode_max_len=64,
                max_new_tokens=8, kv_page_sz=16)
    eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                            buckets=(16, 32), prefill_rows=2)
    eng.warmup_decode()
    return eng


def serve(eng, tracer=None, salt=0):
    """A batch that walks every path of the round: cold prefills, a
    partial hit (chunk), a full hit with a partial tail page (COW), and
    more streams than slots (so somebody waits for a seat).  ``salt``: a
    prefix of its own, so that a second batch on the shared engine finds
    nothing of the first in the index."""
    if tracer is not None:
        eng.tracer = tracer
    b = DecodeBatcher(eng).start()
    b.eos_id = -1
    prompts = [[s + salt for s in SHARED] + [40 + i, 50 + i, 60 + i]
               for i in range(5)]
    streams = [b.submit_ids(p, max_new_tokens=6) for p in prompts]
    streams[0].result(timeout=120)
    # a full hit with a partial tail page (COW), while the fifth stream —
    # the one that waited for a seat — keeps the worker busy
    streams.append(b.submit_ids(prompts[0], max_new_tokens=6))
    for s in streams:
        s.result(timeout=120)
    b.stop()
    assert eng.leak_check()["ok"]
    return streams


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """The batch served inside a real profiler session; the session's
    records, its streams and its xplane file."""
    tr = engine.tracer
    assert tr is obs_trace.get_tracer() and not tr.enabled
    tr.clear()
    out = str(tmp_path_factory.mktemp("profile"))
    real = jax.block_until_ready

    def a_device_step(x):
        # bert-tiny on the CPU answers in under a millisecond, and a round
        # is then so short that the tracer's own 15-20 us between two
        # leaves are 5 % of it; a device whose programs take 8 ms (the
        # chip's take 20-130) leaves the leaves' share to the code
        time.sleep(0.008)
        return real(x)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_mod.jax, "block_until_ready", a_device_step)
        jax.profiler.start_trace(out)
        try:
            streams = serve(engine)
        finally:
            jax.profiler.stop_trace()
    records = tr.records()
    tr.clear()
    xplane = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return {"records": records, "streams": streams, "xplane": xplane[-1]}


def worker_leaves(records):
    leaves = [r for r in records if worker_leaf(r["name"])]
    assert len({r["tid"] for r in leaves}) == 1, "leaves of ONE worker thread"
    return sorted(leaves, key=lambda r: r["t0"])


# ---------------------------------------------------------------- the switch

def test_the_tracer_follows_jaxs_own_test_of_a_session(tmp_path):
    """Loud, not silent: if a JAX upgrade takes ``is_enabled`` away,
    ``follow_profiler`` raises (or this flips no more) — a tracer that
    records nothing would pass every other test's 'off' half."""
    assert jax.profiler.TraceAnnotation.is_enabled() is False
    tr = Tracer(enabled=False)
    assert tr.leaf("x") is _NULL_SPAN and not tr.recording
    assert tr.follow_profiler() is tr and not tr.recording
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tr.recording
        with tr.leaf("x", {"replica": 0}) as sp:
            assert sp and sp is not _NULL_SPAN
            sp.set(rows=2)
        assert not Tracer(enabled=False).recording   # never followed: off
        # span()/mark()/record() stay behind `enabled`: a session is not
        # --trace
        assert tr.span("y") is _NULL_SPAN
    finally:
        jax.profiler.stop_trace()
    assert not tr.recording and tr.leaf("x") is _NULL_SPAN
    (rec,) = tr.records()
    assert rec["name"] == "x" and rec["attrs"] == {"replica": 0, "rows": 2}


def test_leaves_stop_when_the_trace_stops_not_when_it_is_written(
        tmp_path, monkeypatch):
    """``stop_trace`` takes seconds to write a real trace out, and JAX
    keeps its session object until then; a worker that served on would
    fill the records with a window no device trace covers."""
    from jax._src import profiler as jax_profiler

    tr = Tracer(enabled=False).follow_profiler()
    seen = []
    real = jax_profiler._write_perfetto_trace_file

    def while_writing(log_dir):
        seen.append((tr.recording, tr.leaf("late") is _NULL_SPAN))
        return real(log_dir)

    monkeypatch.setattr(jax_profiler, "_write_perfetto_trace_file",
                        while_writing)
    jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
    with tr.leaf("in_time"):
        pass
    jax.profiler.stop_trace()
    assert seen == [(False, True)]
    assert [r["name"] for r in tr.records()] == ["in_time"]


def test_a_jax_without_that_test_is_refused_loudly(monkeypatch):
    monkeypatch.delattr(jax.profiler.TraceAnnotation.__mro__[1],
                        "is_enabled")
    with pytest.raises(AttributeError, match="is_enabled"):
        Tracer(enabled=False).follow_profiler()


# ------------------------------------------------------------- session on

def test_leaves_cover_the_workers_wall_time(traced):
    leaves = worker_leaves(traced["records"])
    wall = max(r["t0"] + r["dur"] for r in leaves) - leaves[0]["t0"]
    covered = sum(r["dur"] for r in leaves)
    assert covered / wall >= 0.95, (covered, wall)


def test_no_leaf_nests_in_another(traced):
    leaves = worker_leaves(traced["records"])
    assert all(r["depth"] == 0 for r in leaves)
    for a, b in zip(leaves, leaves[1:]):
        assert a["t0"] + a["dur"] <= b["t0"] + 1e-9, (a["name"], b["name"])


@pytest.mark.parametrize("name", LEAF_NAMES)
def test_every_leaf_of_the_round_is_recorded_with_its_cause(traced, name):
    hits = [r for r in traced["records"] if r["name"] == name]
    assert hits, f"no {name} leaf in a batch that walks every path"
    for r in hits:
        assert r["attrs"]["replica"] == 0 and r["attrs"]["round"] >= 1
    a = hits[0]["attrs"]
    if name == "admit":
        assert a["seated"] >= 1 and a["waiting"] >= 0
    elif name.endswith(".fetch"):
        assert a["bytes"] > 0
    elif name.endswith(".emit"):
        assert a["rows"] >= 1
    elif name.endswith(".dispatch"):
        assert a["phase"] in ("prefill", "decode")     # warmed: no compile


def test_a_served_launch_hands_the_host_ids_not_logits(traced, engine):
    """What a served launch's ``<p>.fetch`` moves is the chosen id of each
    row — 4 bytes a row, plus a family's expert counts (BERT has none) —
    and never the ``[rows, vocab]`` float32 logits; its ``<p>.emit`` still
    says how many rows it advanced."""
    recs = traced["records"]
    vocab = engine.cfg.vocab_size
    for call, rows in (("decode", engine.slots),
                       ("prefill", engine.prefill_rows),
                       ("chunk", engine.prefill_rows)):
        fetched = [r["attrs"]["bytes"] for r in recs
                   if r["name"] == call + ".fetch"]
        assert fetched and set(fetched) == {rows * 4}, (call, fetched)
        assert rows * 4 < rows * vocab * 4
        emits = [r["attrs"] for r in recs if r["name"] == call + ".emit"]
        assert emits and all(1 <= a["rows"] <= rows for a in emits)
    worker = decode_host_phases(recs)["0"]
    assert worker["decode_fetch_bytes_per_step"] == engine.slots * 4
    assert (f"fetched per decode step: {engine.slots * 4} bytes in "
            in format_decode_table({"0": worker}))


def test_rounds_count_up_and_a_rounds_leaves_share_its_number(traced):
    leaves = worker_leaves(traced["records"])
    rounds = [r["attrs"]["round"] for r in leaves]
    assert rounds == sorted(rounds)
    steps = [r["attrs"]["round"] for r in leaves
             if r["name"] == "decode.dispatch"]
    assert len(steps) == len(set(steps)), "one decode step a round"


def test_one_request_record_per_finished_stream(traced):
    reqs = [r for r in traced["records"] if r["name"] == "request"]
    by_rid = {r["attrs"]["rid"]: r["attrs"] for r in reqs}
    assert len(reqs) == len(by_rid) == len(traced["streams"])
    exemplars = set()
    for r in traced["records"]:
        if r["name"].endswith(".dispatch"):
            exemplars.update(r["attrs"].get("request_ids", ()))
    kinds = set()
    for s in traced["streams"]:
        a = by_rid[s.rid]
        assert a["t_submit"] <= a["t_seated"] <= a["t_first_token"] \
            <= a["t_done"]
        assert a["t_submit"] == s.submitted and a["tokens_out"] == 6
        assert s.rid in exemplars
        kinds.add(a["prefix_hit"])
    assert kinds == {"cold", "partial", "full"}
    # more streams than slots: somebody's wait for a seat is a decode round
    waits = sorted(a["t_seated"] - a["t_submit"] for a in by_rid.values())
    assert waits[-1] > 10 * waits[0]


def test_a_stream_that_outlives_the_session_is_owed_its_record(
        engine, tmp_path):
    """A session is seconds long and an answer outlives it: a stream whose
    first token fell inside gets its ``request`` record when it ends —
    finished after the trace stopped, or cut by ``stop()`` — and one that
    was first served after it gets none."""
    tr = engine.tracer
    tr.clear()
    b = DecodeBatcher(engine).start()
    b.eos_id = -1
    jax.profiler.start_trace(str(tmp_path))
    try:
        inside = b.submit_ids([3 + t for t in SHARED], max_new_tokens=12)
        cut = b.submit_ids([4 + t for t in SHARED], max_new_tokens=47)
        next(inside.tokens(timeout=60)), next(cut.tokens(timeout=60))
    finally:
        jax.profiler.stop_trace()
    assert inside.traced and cut.traced and not tr.recording
    after = b.submit_ids([6 + t for t in SHARED] + [9], max_new_tokens=3)
    after.result(timeout=120)
    inside.result(timeout=120)
    b.stop(drain=False)             # `cut` has some 30 tokens to go
    reqs = {r["attrs"]["rid"]: r["attrs"] for r in tr.records()
            if r["name"] == "request"}
    tr.clear()
    assert after.rid not in reqs and not after.traced
    assert reqs[inside.rid]["tokens_out"] == 12
    assert "error" not in reqs[inside.rid]
    a = reqs[cut.rid]
    assert a["t_submit"] <= a["t_seated"] <= a["t_first_token"] <= a["t_done"]
    try:
        cut.result(timeout=0)
        assert "error" not in a and a["tokens_out"] == 47
    except RuntimeError:
        assert a["error"] == "batcher stopped" and 1 <= a["tokens_out"] < 47


def test_a_session_switches_on_neither_hops_nor_memory_samples(traced):
    names = {r["name"] for r in traced["records"]}
    assert "hop" not in names and "hbm" not in names
    assert names <= set(LEAF_NAMES) | {"request"}, names
    for r in traced["records"]:
        assert "hbm_peak" not in r["attrs"]


def test_the_leaves_lie_on_the_profilers_host_plane(traced):
    """The same intervals, under ``bench:`` + the span's name, beside the
    device's operations."""
    from jax.profiler import ProfileData

    seen = {}
    for plane in ProfileData.from_file(traced["xplane"]).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    seen.setdefault(ev.name, []).append(ev.duration_ns)
    for name in LEAF_NAMES:
        mine = [r["dur"] for r in traced["records"] if r["name"] == name]
        theirs = seen.get(ANNOTATION_PREFIX + name, [])
        assert len(theirs) == len(mine), name
        assert sum(theirs) / 1e9 == pytest.approx(sum(mine), rel=0.2,
                                                  abs=2e-3), name
    assert ANNOTATION_PREFIX + "request" not in seen   # none per request


def test_engine_call_phases_are_derived_from_their_leaves(traced):
    """``prefill`` / ``decode`` of the per-replica tables: one observation
    per engine call, from its dispatch's start to its fetch's end."""
    recs = traced["records"]
    s = StepBreakdown.from_records(recs).summary()
    phases = s["serve_by_replica"]["0"]["phases"]
    n_decode = sum(r["name"] == "decode.dispatch" for r in recs)
    n_prefill = sum(r["name"] in ("prefill.dispatch", "chunk.dispatch")
                    for r in recs)
    assert phases["decode"]["count"] == n_decode
    assert phases["prefill"]["count"] == n_prefill
    parts = sum(r["dur"] for r in recs
                if r["name"].startswith("decode.")
                and r["name"].rpartition(".")[2] in CALL_LEAVES)
    assert phases["decode"]["total_sec"] == pytest.approx(parts, rel=0.05)
    assert s["impls"]["dtype"] == {"float32": n_decode + n_prefill}


# ------------------------------------------------------------ off, --trace

@pytest.mark.parametrize("switch", ["off", "args_trace"])
def test_off_is_off_and_args_trace_keeps_its_streams(engine, switch):
    """No session.  ``off``: nothing is recorded and every span site got
    the shared no-op span, by identity.  ``args_trace``: the leaves record
    (no annotation is needed), and so do the hop stream and the request
    records — what ``--trace`` always meant."""
    on = switch == "args_trace"
    tr = Tracer(enabled=on).follow_profiler()
    handed = []
    leaf = tr.leaf
    tr.leaf = lambda *a, **k: handed.append(leaf(*a, **k)) or handed[-1]
    old = engine.tracer
    try:
        streams = serve(engine, tracer=tr, salt=1 + on)
    finally:
        engine.tracer = old
    assert len(handed) > 40
    names = [r["name"] for r in tr.records()]
    if switch == "off":
        assert names == [] and all(sp is _NULL_SPAN for sp in handed)
        return
    assert all(sp is not _NULL_SPAN for sp in handed)
    assert names.count("request") == len(streams)
    assert set(LEAF_NAMES) <= set(names)
    hops = [r["attrs"]["hop"] for r in tr.records() if r["name"] == "hop"]
    assert hops.count("decode") > 0 and hops.count("complete") == len(streams)


def test_a_submitter_never_waits_on_the_workers_device_wait(
        engine, tmp_path, monkeypatch):
    """Tracing on, the worker inside a (slowed) ``block_until_ready``: the
    submitting thread shares no lock with that span, so ``submit_ids``
    returns at once."""
    in_wait = threading.Event()
    real = jax.block_until_ready

    def slow(x):
        in_wait.set()
        try:
            time.sleep(0.1)
            return real(x)
        finally:
            in_wait.clear()

    monkeypatch.setattr(decode_mod.jax, "block_until_ready", slow)
    engine.tracer.clear()
    b = DecodeBatcher(engine).start()
    b.eos_id = -1
    jax.profiler.start_trace(str(tmp_path))
    try:
        streams = [b.submit_ids(SHARED + [60, 61], max_new_tokens=12)]
        took = []
        for i in range(5):
            assert in_wait.wait(timeout=30)
            t0 = time.perf_counter()
            streams.append(b.submit_ids(SHARED + [70 + i], max_new_tokens=2))
            took.append(time.perf_counter() - t0)
            time.sleep(0.12)            # into the next device wait
        for s in streams:
            s.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
        b.stop()
        engine.tracer.clear()
    assert max(took) < 0.02, took


# ------------------------------------------------ KV read amplification

def test_dispatch_leaves_count_what_attention_covers_and_what_is_live(
        traced, engine):
    """``decode.dispatch`` and ``chunk.dispatch`` carry the cached positions
    the call's attention covers (rows x extent) beside the positions that
    are live; ``summarize`` prints their ratio per decode step."""
    recs = traced["records"]
    dec = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"]
    chk = [r["attrs"] for r in recs if r["name"] == "chunk.dispatch"]
    assert dec and chk
    extents = {r * engine.page_sz * engine.slots for r in engine.decode_rungs}
    for a in dec:
        assert a["kv_positions_read"] in extents
        assert 0 < a["kv_positions_live"] <= a["kv_positions_read"]
    # 19-token prompts and 6 new tokens stay on the second rung of four
    assert {a["kv_positions_read"] for a in dec} == {sorted(extents)[1]}
    for a in chk:
        assert a["kv_positions_read"] == engine.prefill_rows * engine.max_len
        assert 0 < a["kv_positions_live"] <= a["kv_positions_read"]
    worker = decode_host_phases(recs)["0"]
    amp = worker["kv_read_amplification"]
    assert amp == pytest.approx(
        sum(a["kv_positions_read"] for a in dec)
        / sum(a["kv_positions_live"] for a in dec), abs=1e-3)
    assert 1.0 <= amp < engine.max_len
    assert worker["chunk_kv_read_amplification"] >= 1.0
    text = format_decode_table({"0": worker})
    assert f"KV read amplification per decode step: {amp:.3f}" in text


def test_summarize_prints_the_share_of_decode_steps_at_each_row_rung(
        traced, engine):
    """``decode.dispatch``'s ``rows`` is the row rung the step launched
    (an engine of four slots has the one); ``summarize`` prints the share
    of decode steps at each, in the order of the rungs."""
    recs = traced["records"]
    worker = decode_host_phases(recs)["0"]
    assert engine.row_rungs == (engine.slots,)
    assert worker["decode_row_rungs"] == {str(engine.slots): 1.0}
    assert (f"decode steps by rows launched: 100.0% at {engine.slots}"
            in format_decode_table({"0": worker}))
    # the same steps as an engine of 128 slots would state them: every
    # fourth at the top rung
    steps = 0
    rewritten = []
    for r in recs:
        if r["name"] == "decode.dispatch" \
                and r["attrs"].get("phase") != "compile":
            r = {**r, "attrs": {**r["attrs"],
                                "rows": 128 if steps % 4 == 3 else 16}}
            steps += 1
        rewritten.append(r)
    worker = decode_host_phases(rewritten)["0"]
    assert worker["steps"] == steps >= 8
    top, small = steps // 4 / steps, (steps - steps // 4) / steps
    assert worker["decode_row_rungs"] == {
        "16": round(small, 4), "128": round(top, 4)}
    assert (f"decode steps by rows launched: {round(small, 4):.1%} at 16, "
            f"{round(top, 4):.1%} at 128"
            in format_decode_table({"0": worker}))


# ------------------------------------------------- trace_tpu.py summarize

def test_summarize_prints_the_decode_workers_host_phase_table():
    """On a file recorded by ``serve_tpu.py``-style serving with
    ``--trace true`` (warm-up compiles, one batch): per decode step, each
    leaf's mean and p95, and the host-exposed share of the round."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "trace_tpu.py"), "summarize",
         RECORDED], capture_output=True, text=True, check=True, cwd=ROOT)
    lines = out.stdout.splitlines()
    head = next(ln for ln in lines if ln.startswith("decode worker"))
    assert head.startswith("decode worker, replica 0: 15 decode steps")
    assert "host-exposed" in head and "ms/step" in head
    table = {ln.split()[0]: ln.split()[1:] for ln in lines
             if ln.startswith("  ") and ln.split()[0] in LEAF_NAMES}
    assert set(table) == set(LEAF_NAMES)
    assert table["decode.dispatch"][0] == "15"       # compiles left out
    assert table["decode.emit"][0] == "15"
    # the bytes a decode step's fetch moved, beside its milliseconds (this
    # file was recorded when the fetch still was the logits: 4 x 24 x 4)
    fetched = next(ln for ln in lines if "fetched per decode step" in ln)
    assert fetched.split(":")[1].split()[:4] == [
        "384", "bytes", "in", f"{float(table['decode.fetch'][2]):.3f}"]
    as_json = subprocess.run(
        [sys.executable, os.path.join(ROOT, "trace_tpu.py"), "summarize",
         RECORDED, "--json"], capture_output=True, text=True, check=True,
        cwd=ROOT)
    worker = json.loads(as_json.stdout)["decode_worker"]["0"]
    with open(RECORDED, encoding="utf-8") as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    assert worker == decode_host_phases(recs)["0"]
    assert worker["steps"] == 15
    assert 0.0 < worker["host_exposed_share"] < 1.0
    waited = sum(v["ms_per_step"] for k, v in worker["leaves"].items()
                 if k.endswith(".device_wait"))
    assert worker["host_exposed_ms_per_step"] == pytest.approx(
        1e3 * worker["wall_sec"] / 15 - waited, abs=1e-2)
    assert format_decode_table({"0": worker}).splitlines()[0] == head
