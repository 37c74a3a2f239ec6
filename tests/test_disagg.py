"""Disaggregated prefill/decode pool tests: bitwise parity of the
pool-split serving path against interleaved decode (local AND socket
handoff transports), the staged page-custody round trip at the engine
level, the handoff wire framing (torn payloads fail loudly), decode-
replica death mid-storm (orphans re-prefill and hand off again, no
token lost or duplicated), the ``handoff`` hop-chain contract, the
controller's pool-split law on an injected clock, and the live
``set_prefill_share`` re-split.

All three engines run IDENTICAL bert-tiny weights (same seed), so the
interleaved single-batcher output is the exact oracle for every
disaggregated storm: greedy decode is deterministic, and the handoff
moves raw cache bytes — a correct custody transfer cannot change one
token."""
import os
import socket
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab  # noqa: E402
from pdnlp_tpu.obs.decision import validate_decisions  # noqa: E402
from pdnlp_tpu.obs.request import chain_issues, validate_chains  # noqa: E402
from pdnlp_tpu.obs.trace import Tracer  # noqa: E402
from pdnlp_tpu.serve import (  # noqa: E402
    DecodeBatcher, PagedDecodeEngine, ServeController,
)
from pdnlp_tpu.serve.decode import (  # noqa: E402
    DecodeStream, DisaggDecodeRouter, PrefillWorker,
)
from pdnlp_tpu.serve.handoff import (  # noqa: E402
    ACK_ERR, HandoffChannel, HandoffError, HandoffServer, decode_frame,
    encode_frame,
)
from pdnlp_tpu.serve.kvpage import handoff_owner  # noqa: E402
from pdnlp_tpu.utils.config import Args  # noqa: E402

from tests.test_elastic import FakeClock  # noqa: E402

TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
BUCKETS = (16, 32)


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(TEXTS, size=128))


def make_args(**kw):
    base = dict(model="bert-tiny", decode_slots=4, decode_max_len=48,
                max_new_tokens=8, kv_page_sz=8)
    base.update(kw)
    return Args(**base)


def prompts(n=8, seed=3, lo=4, hi=14, vocab=120):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, n)
    return [rng.integers(5, vocab, int(k)).tolist() for k in lens]


@pytest.fixture(scope="module")
def fleet(tok):
    """THREE warmed paged engines on one tracer — the smallest fleet
    with a real choice on both sides of the split (1+2 or 2+1).  The
    PR-16 budget pattern: stream/unit state lives on each fresh router,
    so every test builds its own DisaggDecodeRouter and only the jit
    caches (prefill buckets, decode, COW, export, import) are shared."""
    tr = Tracer(enabled=True)
    engines = [PagedDecodeEngine(make_args(), tokenizer=tok, mesh=None,
                                 buckets=BUCKETS, tracer=tr)
               for _ in range(3)]
    for e in engines:
        e.warmup_decode()
        e.warmup_handoff()
    return engines


def disagg(fleet, **kw):
    kw.setdefault("prefill_engines", 1)
    kw.setdefault("max_waiting", 32)
    router = DisaggDecodeRouter(fleet, **kw).start()
    for u in router._units:
        u.eos_id = -1  # never stop early: deterministic lengths
    return router


def storm(router, ps, max_new=8, timeout=120):
    streams = [router.submit_ids(p, max_new_tokens=max_new) for p in ps]
    return streams, [s.result(timeout=timeout) for s in streams]


@pytest.fixture(scope="module")
def ref_outs(fleet):
    """Interleaved (single-batcher) greedy outputs for the module's
    canonical prompts — the oracle every disaggregated storm must match
    bitwise."""
    b = DecodeBatcher(fleet[0], max_waiting=32).start()
    b.eos_id = -1
    streams = [b.submit_ids(p, max_new_tokens=8) for p in prompts()]
    outs = [s.result(timeout=120) for s in streams]
    b.stop()
    return outs


def _leak_free(*engines):
    for e in engines:
        lk = e.leak_check()
        assert lk["ok"] and not lk["stream_owners"], lk


# ------------------------------------------------------------ parity

def test_disagg_bitwise_parity_zero_retrace(fleet, ref_outs):
    """THE disaggregation pin: a storm through the split pools emits
    bitwise the tokens interleaved decode emits, every stream crosses
    exactly one audited handoff, no engine compiles post-warmup, and
    every allocator drains to zero."""
    r0 = sum(e.metrics.retraces.value for e in fleet)
    m0 = sum(e.metrics.cache_misses.value for e in fleet)
    router = disagg(fleet)
    streams, outs = storm(router, prompts())
    hs = router.health_summary()
    snap = router.control_snapshot()
    router.stop()
    assert outs == ref_outs
    assert sum(e.metrics.retraces.value for e in fleet) == r0
    assert sum(e.metrics.cache_misses.value for e in fleet) == m0
    assert hs["handoffs"] == len(outs) and hs["handoff_failures"] == 0
    assert hs["by_pool"]["prefill"]["engines"] == 1
    assert hs["by_pool"]["decode"]["engines"] == 2
    assert snap["knobs"] == {"prefill_share": 0.333333,
                             "prefill_share_step": 0.333333}
    assert snap["latency"]["ttft_p99_ms"] is not None
    assert snap["latency"]["inter_token_p99_ms"] is not None
    assert {r["pool"] for r in snap["replicas"].values()} \
        == {"prefill", "decode"}
    report = validate_chains(fleet[0].tracer.records(),
                             [s.rid for s in streams])
    assert report["incomplete"] == {}
    assert report["complete"] == len(streams)
    assert report["handed_off"] == len(streams)
    assert report["streamed"] == len(streams)
    _leak_free(*fleet)


def test_disagg_socket_transport_parity(fleet, ref_outs):
    """The process-split rehearsal: every payload crosses the framed
    loopback socket — parity, ack accounting, and the ``transport``
    attr on each handoff hop."""
    router = disagg(fleet, transport="socket")
    streams, outs = storm(router, prompts())
    servers = list(router._servers.values())
    router.stop()
    assert outs == ref_outs
    assert sum(s.frames_ok for s in servers) == len(outs)
    assert sum(s.frames_err for s in servers) == 0
    rids = {s.rid for s in streams}
    hops = [r["attrs"] for r in fleet[0].tracer.records()
            if r.get("name") == "hop"
            and (r.get("attrs") or {}).get("request_id") in rids
            and (r.get("attrs") or {}).get("hop") == "handoff"]
    assert len(hops) == len(outs)
    for h in hops:
        assert h["transport"] == "socket"
        assert h["pages"] >= 1 and h["bytes"] > 0
    report = validate_chains(fleet[0].tracer.records(), sorted(rids))
    assert report["incomplete"] == {}
    assert report["handed_off"] == len(outs)
    _leak_free(*fleet)


# ----------------------------------------------------- page custody

def test_handoff_custody_round_trip(fleet):
    """The engine-level custody transaction: export -> stage (refs move
    to the ``#handoff`` owner, slot frees immediately) -> discharge; the
    importer seats the payload in a cold reservation and both ledgers
    reconcile to zero."""
    a, b = fleet[0], fleet[1]
    stream = DecodeStream([7, 9, 11, 13, 15, 17], max_new_tokens=8)
    a.attach_stream(0, stream, share=False)
    pk, pv = a.export_pages(0, request_ids=[stream.rid])
    staged, pages = a.begin_handoff(0)
    assert staged == handoff_owner(stream.rid)
    assert len(pages) >= 1
    # the slot is already reusable, but the pages stay pinned under the
    # staged owner — the ledger names exactly what a crash would strand
    lk = a.leak_check()
    assert staged in lk["stream_owners"]
    a.allocator.release_owner(staged)
    _leak_free(a)
    b.attach_stream(2, stream, share=False)
    b.import_pages(2, pk, pv, request_ids=[stream.rid])
    b.detach_slot(2)
    _leak_free(b)
    # geometry is validated loudly BEFORE anything writes
    with pytest.raises(HandoffError, match="page geometry"):
        b.import_pages(b.slots, pk[:, :1], pv[:, :1])
    with pytest.raises(ValueError, match="empty slot"):
        a.begin_handoff(0)


def test_disagg_ctor_validation(fleet, tok):
    with pytest.raises(ValueError, match=">= 2 engines"):
        DisaggDecodeRouter([fleet[0]])
    with pytest.raises(ValueError, match="transport"):
        DisaggDecodeRouter(fleet, transport="carrier-pigeon")
    # the split is clamped so that each role keeps at least one engine
    for asked, want in ((99, len(fleet) - 1), (0, 1)):
        r = DisaggDecodeRouter(fleet, prefill_engines=asked)
        roles = [type(u).__name__ for u in r._units]
        assert roles == ["PrefillWorker"] * want \
            + ["DecodeBatcher"] * (len(fleet) - want)


# ---------------------------------------------------- wire framing

def test_handoff_frame_round_trip_and_torn_payloads():
    meta = {"rid": "r-1", "pos": 7, "next_token": 42, "n_pages": 2}
    k = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    v = (np.arange(24, dtype=np.int8) - 5).reshape(2, 3, 4)
    frame = encode_frame(meta, k, v)
    m2, k2, v2 = decode_frame(frame)
    assert m2 == meta
    assert k2.dtype == np.float32 and np.array_equal(k2, k)
    assert v2.dtype == np.int8 and np.array_equal(v2, v)
    with pytest.raises(HandoffError, match="bad magic"):
        decode_frame(b"HTTP" + frame[4:])
    with pytest.raises(HandoffError, match="torn handoff payload"):
        decode_frame(frame[:-3])
    flipped = bytearray(frame)
    flipped[len(frame) // 2] ^= 0xFF
    with pytest.raises(HandoffError, match="torn handoff payload"):
        decode_frame(bytes(flipped))


def test_handoff_socket_server_acks_and_refusals():
    got = []
    k = np.ones((1, 2, 2), np.float32)
    v = np.zeros((1, 2, 2), np.float32)
    with HandoffServer(
            lambda m, pk, pv: got.append((m, pk.copy(), pv.copy()))) as srv:
        with HandoffChannel(srv.address) as ch:
            ch.send({"rid": "a"}, k, v)
            ch.send({"rid": "b"}, k, v)
        assert srv.frames_ok == 2 and srv.frames_err == 0
        # garbage on the wire is NACKed, never imported
        with socket.create_connection(srv.address, timeout=5) as raw:
            raw.sendall(b"JUNKJUNKJUNK")
            assert raw.recv(2) == ACK_ERR
    assert [m["rid"] for m, _, _ in got] == ["a", "b"]
    assert np.array_equal(got[0][1], k)

    def refuse(m, pk, pv):
        raise RuntimeError("no seat")

    with HandoffServer(refuse) as srv:
        with HandoffChannel(srv.address) as ch:
            with pytest.raises(HandoffError, match="rejected"):
                ch.send({"rid": "c"}, k, v)
        assert srv.frames_err == 1


# ---------------------------------------------- hop-chain contract

def H(hop, **kw):
    return {"attrs": {"hop": hop, **kw}}


def test_chain_rules_catch_handoff_violations():
    """The handoff chain rule fires on a synthetic violation and stays
    silent on the legal shapes — including the kill-recovery chain."""
    ok = [H("admit"), H("prefill"), H("handoff", pages=3), H("decode"),
          H("complete")]
    assert chain_issues(ok) == []
    recovery = [H("admit"), H("prefill"), H("handoff"), H("decode"),
                H("requeue"), H("prefill"), H("handoff"), H("decode"),
                H("complete")]
    assert chain_issues(recovery) == []
    bad = [H("admit"), H("handoff"), H("decode"), H("complete")]
    assert any("'handoff' hop with no earlier 'prefill'" in i
               for i in chain_issues(bad))


# ---------------------------------------------- controller split law

class FakeDisaggRouter:
    """Router-shaped double exposing exactly what the pool-split law
    consumes: the ``prefill_share`` knob pair, the per-pool backlogs,
    and the two latency signals — quantized exactly like the real
    router, so actuated targets and re-sensed values compare equal."""

    def __init__(self, n=3):
        self.n = n
        self.k = 1
        self.pb = 0.0
        self.db = 0.0
        self.ttft = 40.0
        self.itok = 12.0
        self.applied = []
        self.tracer = Tracer(enabled=True)

    @property
    def _step(self):
        return round(1.0 / self.n, 6)

    def knob_values(self):
        return {"prefill_share": round(self.k * self._step, 6),
                "prefill_share_step": self._step}

    def apply_knob(self, name, value):
        if name != "prefill_share":
            raise KeyError(name)
        self.k = max(1, min(self.n - 1, int(round(float(value) * self.n))))
        self.applied.append((name, round(self.k * self._step, 6)))

    def control_snapshot(self):
        return {
            "router": {"requests_total": 0, "deadline_expired_total": 0,
                       "queue_depth": 0.0, "admission": {}},
            "active": 1, "standby": 0,
            "knobs": self.knob_values(),
            "latency": {"ttft_p50_ms": self.ttft,
                        "ttft_p99_ms": self.ttft,
                        "inter_token_p50_ms": self.itok,
                        "inter_token_p99_ms": self.itok},
            "by_pool": {"prefill": {"backlog": self.pb},
                        "decode": {"backlog": self.db}},
        }


def _split_controller(n=3, **kw):
    r = FakeDisaggRouter(n=n)
    clk = FakeClock()
    kw.setdefault("eval_window_s", 5.0)
    c = ServeController(r, clock=clk, tracer=r.tracer, **kw)
    assert c.step() is None  # first tick only primes the counter deltas
    clk.advance(1.0)
    return c, r, clk


def _tick(c, r, clk, pb=0.0, db=0.0, dt=1.0):
    r.pb, r.db = pb, db
    s = c.step()
    clk.advance(dt)
    return s


def test_split_law_grows_and_shrinks_on_sustained_backlog():
    """Sustained prefill backlog for ``split_patience`` ticks grows the
    prefill pool ONE quantum (judged against the decode side's
    ``inter_token_p99_ms``); sustained decode backlog shrinks it back
    (judged against ``ttft_p99_ms``); flapping pressure resets the
    patience counter; every decision chain closes."""
    c, r, clk = _split_controller(n=3)
    # flapping: pressure / neutral / pressure / neutral — no verdict
    _tick(c, r, clk, pb=5.0)
    _tick(c, r, clk)
    _tick(c, r, clk, pb=5.0)
    _tick(c, r, clk)
    assert r.applied == []
    # two CONSECUTIVE pressure ticks: one quantum toward prefill
    _tick(c, r, clk, pb=5.0)
    _tick(c, r, clk, pb=5.0)
    assert r.applied == [("prefill_share", 0.666666)]
    assert r.knob_values()["prefill_share"] == 0.666666
    # the grow's eval window (signal flat -> kept), then the cooldown
    clk.advance(11.0)
    _tick(c, r, clk, db=5.0)
    _tick(c, r, clk, db=5.0)
    assert r.applied[-1] == ("prefill_share", 0.333333)
    # let the shrink's own eval window close before the audit
    clk.advance(6.0)
    for _ in range(2):
        _tick(c, r, clk)
    c.stop()
    rep = validate_decisions(r.tracer.records())
    assert rep["incomplete"] == {}
    assert rep["by_knob"].get("prefill_share", 0) >= 2


def test_split_law_never_empties_a_pool():
    """n=2: the only grow target (1.0) would empty the decode pool —
    the clamp guard turns the law into a no-op, not a ghost actuation
    the eval window would chase."""
    c, r, clk = _split_controller(n=2)
    for _ in range(5):
        _tick(c, r, clk, pb=9.0)
    assert r.applied == []
    c.stop()


# ------------------------------------------------------ live re-split

def test_live_resplit_rebalances_and_preserves_parity(fleet, ref_outs):
    """``set_prefill_share`` re-roles engines on a live router: the
    split moves, a post-split storm still matches the oracle bitwise,
    and nothing recompiles (engines keep their jit caches across the
    re-role)."""
    router = disagg(fleet)
    _, outs1 = storm(router, prompts())
    assert outs1 == ref_outs
    applied = router.set_prefill_share(0.666666)
    assert applied == 0.666666
    assert router.knob_values()["prefill_share"] == 0.666666
    for u in router._units:
        u.eos_id = -1  # rebuilt units come back with the real sep id
    hs = router.health_summary()
    assert hs["by_pool"]["prefill"]["engines"] == 2
    assert hs["by_pool"]["decode"]["engines"] == 1
    r0 = sum(e.metrics.retraces.value for e in fleet)
    m0 = sum(e.metrics.cache_misses.value for e in fleet)
    _, outs2 = storm(router, prompts())
    assert outs2 == ref_outs
    assert sum(e.metrics.retraces.value for e in fleet) == r0
    assert sum(e.metrics.cache_misses.value for e in fleet) == m0
    # quantization clamps: 0.9 * 3 rounds to 3 -> floored to n-1
    assert router.set_prefill_share(0.9) == 0.666666
    assert router.set_prefill_share(0.1) == 0.333333
    with pytest.raises(ValueError, match="unknown disagg knob"):
        router.apply_knob("draft_k", 3)
    router.stop()
    _leak_free(*fleet)


# ------------------------------------------------------------- chaos

def test_decode_kill_mid_storm_recovers(fleet):
    """Chaos: a decode-role replica dies mid-storm — its orphans
    re-enter the front door, re-prefill, hand off AGAIN to the
    survivor, and the storm's output stays bitwise the oracle's (no
    lost, no duplicated tokens); every chain validates and the
    survivors' allocators drain clean."""
    ps = prompts(n=12, seed=7)
    b = DecodeBatcher(fleet[0], max_waiting=32).start()
    b.eos_id = -1
    refs = [s.result(timeout=120)
            for s in [b.submit_ids(p, max_new_tokens=16) for p in ps]]
    b.stop()
    router = disagg(fleet)
    streams = [router.submit_ids(p, max_new_tokens=16) for p in ps]
    victim = router._units[1]  # a decode-role unit (unit 0 prefills)
    deadline = time.monotonic() + 60
    while victim.metrics.tokens_out_total.value < 10 \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    router.kill(1, RuntimeError("chaos: decode engine evicted"))
    outs = [s.result(timeout=180) for s in streams]
    router.stop()
    assert victim.dead
    assert outs == refs, "kill recovery duplicated or lost tokens"
    report = validate_chains(fleet[0].tracer.records(),
                             [s.rid for s in streams])
    assert report["incomplete"] == {}
    assert report["complete"] == len(streams)
    assert report["handed_off"] == len(streams)
    # SURVIVOR ledgers reconcile; the victim's allocator died with its
    # cache (the established kill contract — see test_kvpage's paged
    # kill test: only survivors are audited)
    _leak_free(fleet[0], fleet[2])


def test_no_live_prefill_fails_loudly(fleet):
    router = disagg(fleet)
    router.kill(0)  # the only prefill-role unit
    deadline = time.monotonic() + 10
    while not router._units[0].dead and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="no live prefill"):
        router.submit_ids([5, 6, 7])
    router.stop()
