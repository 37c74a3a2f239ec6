"""The decode worker's host phases as LEAF spans (``Tracer.leaf``): on the
tracer's ring buffer and, as ``bench:<name>`` annotations, on the JAX
profiler's host plane — switched on by a profiler session alone, without
``Args.trace``.  A bert-tiny paged engine behind a ``DecodeBatcher`` on the
CPU, under a REAL ``jax.profiler.start_trace``.

Pinned here: the leaves cover the worker's round and never nest; one
``request`` record per finished stream, joinable to its dispatch leaf by
``rid``; what a session does NOT switch on (the per-token hop stream, the
memory sample); that off is off (every span falsy, the ring of spans empty;
the shared no-op span, by identity, on a thread with no round open); that
a submitter never waits on the worker's device wait; the engine-call phase
tables derived from the leaves; ``trace_tpu.py summarize``'s host-phase
table on a recorded file; and the worker's own account of every round,
profiler or not (``Tracer.open_round`` / ``rounds``, ``round_account``,
the build counters)."""
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
    format_round_table, recent_round_account, round_account, round_rows,
    worker_leaf,
)
from pdnlp_tpu.obs.trace import (
    _NULL_SPAN, ANNOTATION_PREFIX, BUILDS, ROUND_COLUMNS, ROUND_KINDS,
    ROUND_LEAVES, Tracer,
)
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
    waits = []

    def a_device_step(x):
        waits.append(1)
        # bert-tiny on the CPU answers in under a millisecond, and a round
        # is then so short that the tracer's own 15-20 us between two
        # leaves are 5 % of it; a device whose programs take 8 ms (the
        # chip's take 20-130) leaves the leaves' share to the code
        time.sleep(0.008)
        return real(x)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_mod.jax, "block_until_ready", a_device_step)
        jax.profiler.start_trace(out)
        began = tr.now()
        try:
            streams = serve(engine)
        finally:
            jax.profiler.stop_trace()
    records = tr.records()
    tr.clear()
    xplane = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return {"records": records, "streams": streams, "xplane": xplane[-1],
            "rounds": tr.rounds(began), "waits": len(waits)}


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
    """No session.  ``off``: nothing is recorded, no site built an
    attribute — every handed span is falsy and the ring of spans is empty
    (inside the worker's round the span is its tally span; a thread with no
    round open still gets the shared no-op span, by identity).
    ``args_trace``: the leaves record (no annotation is needed), and so do
    the hop stream and the request records — what ``--trace`` always
    meant."""
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
        assert names == [] and not any(handed)
        assert all(sp is not _NULL_SPAN for sp in handed)   # all in a round
        assert leaf("decode.dispatch", {"replica": 0}) is _NULL_SPAN
        return
    assert all(handed) and all(sp is not _NULL_SPAN for sp in handed)
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
    the call's attention covers beside the positions that are live — a
    decode step of this family walks the seated rows' own pages
    (``attend="kernel"``): their live positions, each row's rounded up to a
    page; a chunk covers rows x the whole extent —; ``summarize`` prints
    their ratio per decode step and the share of steps in each form."""
    recs = traced["records"]
    dec = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"]
    chk = [r["attrs"] for r in recs if r["name"] == "chunk.dispatch"]
    assert dec and chk
    ps = engine.page_sz
    for a in dec:
        assert a["attend"] == "kernel"
        assert a["kv_positions_read"] % ps == 0
        assert 0 < a["kv_positions_live"] <= a["kv_positions_read"]
        assert a["kv_positions_read"] - a["kv_positions_live"] \
            <= engine.slots * (ps - 1)
    # 19-token prompts and 6 new tokens: two pages a seated row, under the
    # second rung of four that every launched row was read at before
    assert {a["kv_positions_read"] for a in dec} <= {
        2 * ps * n for n in range(1, engine.slots + 1)}
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
    assert worker["decode_attend"] == {"kernel": 1.0}
    text = format_decode_table({"0": worker})
    assert f"KV read amplification per decode step: {amp:.3f}" in text
    assert "decode steps by attention: 100.0% kernel" in text


@pytest.mark.parametrize("kind,attend", [("float", "kernel"),
                                         ("int8", "gather"),
                                         ("mesh", "gather")])
def test_the_dispatch_leaf_says_which_form_of_attention_the_step_took(
        kind, attend, monkeypatch):
    """``attend`` is the counter of how often the kernel engages, and it is
    the program's own word (``decoder.attend_form``, asked by the step and
    by the span alike): a float pool of the BERT family reads the ALIVE
    rows' pages x ``page_sz`` exactly; an int8 pool, and an engine whose
    programs run over a mesh of several devices (Mosaic refuses a kernel
    there), keep the gathered form — the kernel is never traced — and the
    old product, rows x the page rung x ``page_sz``: what a family with an
    attention core of its own states too (``*_kv_read_amplification`` read
    what they read)."""
    from pdnlp_tpu.ops import paged
    from pdnlp_tpu.parallel import make_mesh

    if attend == "gather":
        def refuse(*a, **k):
            raise AssertionError("the kernel was traced")
        monkeypatch.setattr(paged, "paged_decode", refuse)
    tok = WordPieceTokenizer(build_vocab(TEXTS, size=128))
    args = Args(model="bert-tiny", decode_slots=4, decode_max_len=64,
                max_new_tokens=8, kv_page_sz=16,
                kv_dtype="int8" if kind == "int8" else "auto")
    tr = Tracer(enabled=True)
    eng = PagedDecodeEngine(
        args, tokenizer=tok, buckets=(16,), prefill_rows=2, tracer=tr,
        mesh=make_mesh(num_devices=2) if kind == "mesh" else None)
    ps = [SHARED[:7], SHARED[:16] + [30, 31]]
    for slot, p in zip((0, 2), ps):
        eng.attach_stream(slot, decode_mod.DecodeStream(p, 8), share=False)
    eng.prefill_ids(ps, [0, 2])
    tr.clear()
    pos = [7, 0, 18, 0]
    eng.decode_batch([5, 0, 6, 0], pos, live=2)
    a, = [r["attrs"] for r in tr.records() if r["name"] == "decode.dispatch"]
    assert a["attend"] == attend and a["rows"] == 4
    assert a["kv_positions_live"] == 8 + 19
    rung = next(g for g in eng.decode_rungs if g * 16 > 18)
    assert a["kv_positions_read"] == (
        (1 + 2) * 16 if attend == "kernel" else 4 * rung * 16)


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


# ------------------------------------- the worker's own account of a round

LEAF_CELLS = ROUND_LEAVES


@pytest.fixture(scope="module")
def untraced(engine):
    """The batch served with NO session and no ``--trace``, on a tracer of
    its own: the spans its sites were handed, the barriers ``_fetch`` made,
    the ring of spans and the ring of rounds."""
    tr = Tracer(enabled=False).follow_profiler()
    handed, waits = [], []
    leaf = tr.leaf
    tr.leaf = lambda *a, **k: handed.append(leaf(*a, **k)) or handed[-1]
    real = jax.block_until_ready
    old = engine.tracer
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_mod.jax, "block_until_ready",
                   lambda x: waits.append(1) or real(x))
        try:
            streams = serve(engine, tracer=tr, salt=7)
        finally:
            engine.tracer = old
    return {"tracer": tr, "handed": handed, "waits": len(waits),
            "streams": streams, "rounds": tr.rounds(),
            "records": tr.records()}


def test_an_untraced_batcher_leaves_a_row_a_round(untraced):
    rows = untraced["rounds"]
    steps = sum(len(s.emitted) for s in untraced["streams"])
    assert len(rows) >= steps // 4      # four slots: a step advances <= 4
    numbers = [r["round"] for r in rows]
    assert numbers == list(range(numbers[0], numbers[0] + len(rows)))
    starts = [r["t0"] for r in rows]
    assert starts == sorted(starts)
    for r in rows:
        assert tuple(r) == ROUND_COLUMNS and r["replica"] == 0
        assert r["other"] >= 0.0 and r["wall"] > 0.0
        parts = sum(r[c] for c in LEAF_CELLS) + r["other"]
        assert parts == pytest.approx(r["wall"], abs=1e-6)
        assert r["cpu_span"] >= r["cpu"] >= 0.0
        assert 1 <= r["live"] <= 4 and 0 <= r["seated"] <= 4
    decoded = [r for r in rows if r["decode.dispatch"] > 0]
    assert decoded and all(r["decode.wait_fetch"] > 0 for r in decoded)
    assert sum(r["seated"] for r in rows) == len(untraced["streams"])


def test_untraced_the_ring_of_spans_stays_empty_and_nothing_is_built(
        untraced):
    assert untraced["records"] == []
    assert len(untraced["handed"]) > 40 and not any(untraced["handed"])
    # one reused tally span a worker thread, never a fresh object a leaf
    assert len({id(sp) for sp in untraced["handed"]}) == 1
    # and off the worker's thread, with no round open: the shared no-op
    assert untraced["tracer"].leaf("decode.dispatch") is _NULL_SPAN


@pytest.mark.parametrize("session", ["untraced", "traced"])
def test_fetch_waits_apart_only_where_the_leaves_record(
        request, session):
    """``_fetch`` untraced is ONE runtime barrier a call (``device_get``):
    ``jax.block_until_ready`` is not called; under a session it is, once
    an engine call."""
    run = request.getfixturevalue(session)
    if session == "untraced":
        assert run["waits"] == 0
        return
    waited = [r for r in run["records"] if r["name"].endswith(".device_wait")]
    assert run["waits"] == len(waited) > 0


def test_under_a_session_the_rows_are_the_leaves_sums(traced):
    """The recording leaves feed the same cells: a row's dispatch /
    ``wait_fetch`` (= device_wait + fetch) / emit / admit are the sums of
    its round's leaves."""
    rows = traced["rounds"]
    assert rows
    sums = {}
    for rec in traced["records"]:
        if worker_leaf(rec["name"]):
            call, _, part = rec["name"].rpartition(".")
            cell = f"{call}.wait_fetch" \
                if part in ("device_wait", "fetch") else rec["name"]
            key = (rec["attrs"]["round"], cell)
            sums[key] = sums.get(key, 0.0) + rec["dur"]
    by_round = {r["round"]: r for r in rows}
    assert {k[0] for k in sums} <= set(by_round)
    for (rnd, name), total in sums.items():
        assert by_round[rnd][name] == pytest.approx(total, abs=1e-6), name
    for r in rows:
        for c in LEAF_CELLS:
            if r[c]:
                assert (r["round"], c) in sums, c
        assert sum(r[c] for c in LEAF_CELLS) + r["other"] \
            == pytest.approx(r["wall"], abs=1e-6)
    # the same account, traced and untraced, side by side
    acc = round_account(rows)
    assert acc["kinds"]["decode"]["parts"]["decode.wait_fetch"]["mean_ms"] \
        >= 8.0      # the fixture's device step sleeps 8 ms


def test_a_served_batchs_rounds_are_named_after_what_they_held(untraced):
    rows = untraced["rounds"]
    for r in rows:
        held = [c for c in ROUND_KINDS[:-1] if r[c + ".dispatch"] > 0]
        assert r["kind"] == (held[0] if held else "host")
    # cold prefills, a partial hit, plain steps; and a full hit's COW,
    # which names a round only where no prompt call shares it
    assert {"prefill", "chunk", "decode"} <= {r["kind"] for r in rows}
    cows = [r for r in rows if r["cow.dispatch"] > 0]
    assert cows and all(r["kind"] in ("prefill", "chunk", "cow")
                        for r in cows)


@pytest.mark.parametrize("calls,kind", [
    (("cow", "prefill", "decode"), "prefill"),   # a cow does not hide it
    (("cow", "chunk", "decode"), "chunk"),
    (("prefill", "chunk", "decode"), "prefill"),
    (("decode", "verify"), "verify"),
    (("cow", "decode"), "cow"),
    (("decode",), "decode"),
    ((), "host"),
])
def test_a_rounds_kind_is_the_first_call_it_held(calls, kind):
    tr = Tracer(enabled=False)
    tr.open_round(3, 11)
    for call in calls:
        with tr.leaf(call + ".dispatch"):
            time.sleep(0.0002)
    with tr.leaf("not.a.leaf.of.the.round"):    # stays in ``other``
        time.sleep(0.0002)
    tr.close_round(live=2, seated=1)
    (row,) = tr.rounds()
    assert (row["kind"], row["replica"], row["round"]) == (kind, 3, 11)
    assert (row["live"], row["seated"]) == (2, 1)
    assert row["other"] >= 0.0002
    assert tr.leaf("decode.dispatch") is _NULL_SPAN     # closed: off again


def test_a_dropped_round_writes_no_row_and_tracers_keep_apart():
    a, b = Tracer(enabled=False), Tracer(enabled=False)
    a.open_round(0, 1)
    assert b.leaf("admit") is _NULL_SPAN    # another tracer's round
    sp = a.leaf("decode.device_wait")       # handed out and dropped
    assert not sp and sp is a.leaf("decode.fetch")
    a.drop_round()
    a.close_round()                         # nothing open: no-op
    assert a.rounds() == [] and b.rounds() == []
    assert a.leaf("admit") is _NULL_SPAN


def test_the_threads_cpu_clock_is_read_by_the_tenth_of_a_second(monkeypatch):
    """On the chip's host a read of the thread's CPU clock is a system call
    of 25 us between other work and ticks in 10 ms steps (PERF.md section
    6, PR 40): no leaf reads it, and a round reads it only where
    ``CPU_EVERY_S`` have passed since the last reading — the row then
    carries the CPU seconds of the span that ends with it; an idle wait's
    CPU time is no round's."""
    reads, now = [], [100.0]
    clock = time.thread_time
    monkeypatch.setattr(obs_trace.time, "thread_time",
                        lambda: reads.append(1) or clock())
    tr = Tracer(enabled=False, clock=lambda: now[0])

    def a_round(i, seconds):
        tr.open_round(0, i)
        with tr.leaf("decode.dispatch"):
            pass
        with tr.leaf("decode.fetch"):
            sum(range(20000))           # the worker's own CPU, in a wait
            now[0] += seconds
        tr.close_round()

    for i in range(1, 11):
        a_round(i, 0.03)                # ten rounds of 30 ms
    assert len(reads) == 1 + 2          # the first round's start, 120, 240 ms
    a_round(11, 0.25)                   # a long round always reads
    assert len(reads) == 4
    tr.open_round(0, 12)
    tr.drop_round()                     # idle: the next round reads anew
    a_round(12, 0.03)
    assert len(reads) == 5
    rows = tr.rounds()
    assert [r["round"] for r in rows if r["cpu_span"]] == [4, 8, 11]
    spans = [r["cpu_span"] for r in rows if r["cpu_span"]]
    assert spans == pytest.approx([0.12, 0.12, 0.06 + 0.25])
    assert all(r["cpu"] == 0.0 for r in rows if not r["cpu_span"])
    assert sum(r["cpu"] for r in rows) > 0.0
    # the spans that were read lie end to end: no round between is left out
    assert sum(spans) == pytest.approx(sum(r["wall"] for r in rows[:11]))


def test_a_build_inside_a_round_is_counted_where_jax_builds(engine):
    """``retraces`` counts a jitted body's Python runs; ``BUILDS`` listens
    to JAX's own ``backend_compile_duration``: a build lands on the
    process's counters, on ``engine.metrics`` and on the round open on
    its thread; a call of a warmed shape builds nothing."""
    import numpy as np

    tr = Tracer(enabled=False).follow_profiler()
    m = engine.metrics
    fn = jax.jit(lambda x: x * 3 + 40)
    x = np.arange(7, dtype=np.float32)
    tok = np.zeros((engine.slots,), np.int32)
    pos = np.full((engine.slots,), 15, np.int32)
    engine._decode_rows(tok, pos, live=0)       # a warmed shape, all dead
    built, secs, traces = (m.executables_built, m.backend_compile_s,
                           m.retraces.value)
    tr.open_round(0, 1)
    jax.block_until_ready(fn(x))
    assert m.executables_built == built + 1 == BUILDS.executables_built
    assert m.backend_compile_s > secs
    jax.block_until_ready(fn(x))                # the same shape again
    engine._decode_rows(tok, pos, live=0)       # the engine's, warmed
    tr.close_round(live=0)
    assert m.executables_built == built + 1 and m.retraces.value == traces
    (row,) = tr.rounds()
    assert row["builds"] == 1 and row["build_s"] > 0.0
    assert 0.0 < row["build_s"] <= row["wall"]
    snap = m.snapshot()["compile_cache"]
    assert snap["executables_built"] == built + 1
    assert snap["backend_compile_s"] > 0
    # outside a round the process still counts; no row takes it
    jax.block_until_ready(jax.jit(lambda x: x * 5 + 41)(x))
    assert m.executables_built == built + 2 and len(tr.rounds()) == 1


def test_the_ring_of_rounds_wraps_without_growing(monkeypatch):
    monkeypatch.setattr(obs_trace, "ROUND_CAPACITY", 8)
    tr = Tracer(enabled=False)
    for i in range(1, 6):
        tr.open_round(0, i)
        tr.close_round()
    assert [r["round"] for r in tr.rounds()] == [1, 2, 3, 4, 5]
    size = len(tr._rounds)
    assert size == 8 * len(ROUND_COLUMNS)
    for i in range(6, 21):
        tr.open_round(0, i)
        with tr.leaf("decode.dispatch"):
            pass
        tr.close_round()
    rows = tr.rounds()
    assert [r["round"] for r in rows] == list(range(13, 21))
    assert len(tr._rounds) == size
    mid = rows[3]["t0"]
    assert [r["round"] for r in tr.rounds(mid)] == [16, 17, 18, 19, 20]
    assert [r["round"] for r in tr.rounds(None, mid)] == [13, 14, 15]


def _row(i, wall, kind="decode", **cells):
    row = dict.fromkeys(ROUND_COLUMNS, 0.0)
    row.update(t0=float(i), wall=wall, replica=0, round=i, kind=kind,
               live=4, seated=0, builds=0)
    row.update(cells)
    return row


def test_round_account_by_hand():
    """Percentiles, the worker's CPU share and ``host_off_cpu``, and the
    longest rounds of made-up rows: 20 plain rounds of 10..29 ms and two
    that held a prefill."""
    rows = [_row(i, (10 + i) * 1e-3, **{
        "decode.dispatch": 1e-3, "decode.wait_fetch": 6e-3,
        "decode.emit": 1e-3, "other": (2 + i) * 1e-3}) for i in range(20)]
    # the thread's clock was read at the ends of rounds 9, 19 and 20: the
    # spans are those rounds' walls summed (145, 245 and 150 ms)
    rows[9].update(cpu=29e-3, cpu_span=0.145)
    rows[19].update(cpu=49e-3, cpu_span=0.245)
    rows.append(_row(20, 0.150, "prefill", **{
        "prefill.dispatch": 4e-3, "prefill.wait_fetch": 91e-3,
        "prefill.emit": 2e-3, "decode.dispatch": 1e-3,
        "decode.wait_fetch": 6e-3, "decode.emit": 1e-3, "admit": 3e-3,
        "other": 42e-3, "cpu": 30e-3, "cpu_span": 0.150, "builds": 2,
        "build_s": 0.040}))
    rows.append(_row(21, 0.050, "prefill", **{
        "prefill.dispatch": 4e-3, "prefill.wait_fetch": 30e-3,
        "decode.dispatch": 1e-3, "decode.wait_fetch": 6e-3,
        "other": 9e-3}))
    acc = round_account(rows)
    assert acc["rounds"] == 22 and acc["builds"] == 2
    assert acc["wall_sec"] == 0.59
    assert list(acc["kinds"]) == ["decode", "prefill"]   # by count
    d = acc["kinds"]["decode"]
    assert d["count"] == 20 and d["builds"] == 0
    assert d["wall_ms"] == {"p50": 19.5, "p90": 27.1, "p99": 28.81,
                            "max": 29.0, "mean": 19.5}
    assert d["parts"]["decode.wait_fetch"] == {"mean_ms": 6.0, "p50_ms": 6.0}
    assert d["parts"]["other"] == {"mean_ms": 11.5, "p50_ms": 11.5}
    assert set(d["parts"]) == {"decode.dispatch", "decode.wait_fetch",
                               "decode.emit", "other"}
    p = acc["kinds"]["prefill"]
    assert p["count"] == 2 and p["builds"] == 2 and p["build_ms"] == 40.0
    assert p["parts"]["prefill.wait_fetch"] == {"mean_ms": 60.5,
                                                "p50_ms": 60.5}
    # on its CPU 108 of the 540 ms that were read = a fifth; of a mean
    # round of 590 / 22 ms that is 5.3636; the waits are 22 x 6 + 121 ms,
    # so host_off_cpu = (590 - 253) / 22 - 5.3636 = 9.9545 ms a round
    assert acc["cpu"] == {"span_sec": 0.54, "share": 0.2,
                          "ms_per_round": 5.3636,
                          "host_off_cpu_ms_per_round": 9.9545}
    longest = acc["longest"]
    assert len(longest) == 16
    assert [r["round"] for r in longest] == [20, 21] + list(range(19, 5, -1))
    top = longest[0]
    assert (top["kind"], top["wall_ms"], top["builds"], top["build_ms"]) \
        == ("prefill", 150.0, 2, 40.0)
    assert (top["cpu_ms"], top["cpu_span_ms"]) == (30.0, 150.0)
    assert (longest[1]["cpu_ms"], longest[1]["cpu_span_ms"]) == (0.0, 0.0)
    assert top["parts_ms"]["other"] == 42.0
    assert sum(top["parts_ms"].values()) == pytest.approx(150.0)
    assert "prefill.wait_fetch" in top["parts_ms"] \
        and "chunk.dispatch" not in top["parts_ms"]
    # rows that carry no reading of the CPU clock: no share is made up
    assert round_account(rows[:9])["cpu"] == {"span_sec": 0.0}
    assert round_account([]) == {
        "rounds": 0, "wall_sec": 0, "builds": 0, "cpu": {"span_sec": 0},
        "kinds": {}, "longest": []}
    json.dumps(acc)
    text = format_round_table({"0": acc})
    assert "decode worker rounds, replica 0: 22 rounds" in text
    assert "on its CPU 20.0% of 0.540s read = 5.364 ms a round; " \
        "host_off_cpu 9.95" in text
    assert "round 20 (prefill, 4 live, 0 seated) 150.000 ms" in text
    assert "on its CPU 30.0 of the 150.0 ms that end with it" in text
    assert "2 builds, 40.0 ms" in text


def test_a_cut_by_time_reads_the_rings_tail(monkeypatch):
    """``rounds(t0)`` finds its first row by bisection over the rounds'
    ends — wrapped ring or not — and hands out the rows a walk over the
    whole ring would; a worker's ``snapshot()`` accounts for its own last
    ``ACCOUNT_SECONDS`` alone."""
    monkeypatch.setattr(obs_trace, "ROUND_CAPACITY", 64)
    now = [50.0]
    tr = Tracer(enabled=False, clock=lambda: now[0])
    for i in range(1, 151):             # replicas 0 and 1 in turn, 0.5 s each
        tr.open_round(i % 2, i)
        now[0] += 0.5
        tr.close_round()
    rows = tr.rounds()
    assert [r["round"] for r in rows] == list(range(87, 151))
    for cut in (0.0, rows[0]["t0"], rows[0]["t0"] + 0.1, rows[30]["t0"],
                rows[-1]["t0"], rows[-1]["t0"] + 0.1, now[0] + 9.0):
        assert tr.rounds(cut) == [r for r in rows if r["t0"] >= cut]
        assert tr.rounds(cut, replica=1) == [
            r for r in rows if r["t0"] >= cut and r["replica"] == 1]
    acc = recent_round_account(tr, 1)
    assert acc["window_sec"] == 30.0
    assert acc["rounds"] == 30          # 60 rounds began in 30 s: half its
    assert {r["replica"] for r in acc["longest"]} == {1}
    now[0] += 29.4                      # only round 150 is left
    assert recent_round_account(tr, 0)["rounds"] == 1


def test_summarize_prints_the_round_table_from_a_flushed_file(
        engine, tmp_path, monkeypatch):
    """``--trace``: ``Tracer.flush`` writes the ring of rounds beside the
    spans, and ``trace_tpu.py summarize`` prints the account an operator
    reads in ``snapshot()["rounds"]`` under the host-phase table."""
    # bert-tiny on the CPU serves the batch in under a tenth of a second
    monkeypatch.setattr(obs_trace, "CPU_EVERY_S", 0.0)
    tr = Tracer(str(tmp_path), enabled=True, process_index=0)
    tr.follow_profiler()
    old = engine.tracer
    try:
        serve(engine, tracer=tr, salt=11)
    finally:
        engine.tracer = old
    path = tr.flush()
    with open(path, encoding="utf-8") as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    rows = round_rows(recs)
    assert len(rows) == len(tr.rounds()) > 5
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "trace_tpu.py"), "summarize",
         path], capture_output=True, text=True, check=True, cwd=ROOT)
    lines = out.stdout.splitlines()
    host = next(i for i, ln in enumerate(lines)
                if ln.startswith("decode worker, replica 0"))
    head = next(i for i, ln in enumerate(lines)
                if ln.startswith("decode worker rounds, replica 0"))
    assert head > host
    assert lines[head].startswith(
        f"decode worker rounds, replica 0: {len(rows)} rounds")
    assert any("host_off_cpu" in ln for ln in lines)
    assert any("longest rounds" in ln for ln in lines)
    as_json = subprocess.run(
        [sys.executable, os.path.join(ROOT, "trace_tpu.py"), "summarize",
         path, "--json"], capture_output=True, text=True, check=True,
        cwd=ROOT)
    assert json.loads(as_json.stdout)["decode_rounds"]["0"] \
        == round_account(rows)
    # a file without rounds (the recorded one predates them) prints none
    old_file = subprocess.run(
        [sys.executable, os.path.join(ROOT, "trace_tpu.py"), "summarize",
         RECORDED], capture_output=True, text=True, check=True, cwd=ROOT)
    assert "decode worker rounds" not in old_file.stdout
