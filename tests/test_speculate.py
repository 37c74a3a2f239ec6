"""Speculative decoding tests: draft-k/verify-1 bitwise greedy parity
against primary-only decode, the zero-retrace guarantee across the
engine pair, drafter-death degrade mid-storm, the draft->verify hop
chain contract, the controller's speculation law (halve / disable /
deepen / auto-revert) on an injected clock, and the router's knob +
exporter surface.

The engine pair runs IDENTICAL bert-tiny weights on both sides (same
seed): with untrained weights a genuinely different drafter never agrees
with the primary's argmax, so the identical pair is what exercises the
accept/commit machinery at a real acceptance ceiling — the parity
contract itself is acceptance-independent (verify-1 commits only the
primary's own greedy tokens); the speedup side is not measured (no
benchmark cell speculates: ROADMAP, Design 8)."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab  # noqa: E402
from pdnlp_tpu.obs.decision import validate_decisions  # noqa: E402
from pdnlp_tpu.obs.exporter import prometheus_lines  # noqa: E402
from pdnlp_tpu.obs.request import chain_issues, validate_chains  # noqa: E402
from pdnlp_tpu.obs.trace import Tracer  # noqa: E402
from pdnlp_tpu.serve import (  # noqa: E402
    DecodeBatcher, DecodeRouter, PagedDecodeEngine,
    ServeController,
)
from pdnlp_tpu.utils.config import Args  # noqa: E402

from tests.test_elastic import FakeClock  # noqa: E402

TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
BUCKETS = (16, 32)
DRAFT_K = 4


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
def spair(tok):
    """ONE warmed primary+drafter paged pair shared by every batcher
    test below (the PR-16 budget pattern: stream state lives on each
    fresh DecodeBatcher, so sharing engines only shares compiled jits).
    One in-memory tracer spans the pair — the batcher records hops
    through ``engine.tracer``, and the chain tests read it back."""
    tr = Tracer(enabled=True)
    eng = PagedDecodeEngine(make_args(), tokenizer=tok, mesh=None,
                            buckets=BUCKETS, tracer=tr)
    dr = PagedDecodeEngine(make_args(), tokenizer=tok, mesh=None,
                           buckets=BUCKETS, tracer=tr,
                           prefix_share=False)
    b = DecodeBatcher(eng, drafter=dr, draft_k=DRAFT_K)
    b.warmup()  # primary decode + drafter decode + verify at k+1, once
    return eng, dr


def spec_batcher(spair, **kw):
    eng, dr = spair
    kw.setdefault("draft_k", DRAFT_K)
    return DecodeBatcher(eng, max_waiting=16, drafter=dr, **kw).start()


def run_streams(batcher, ps, max_new=8, eos=-1, timeout=120):
    batcher.eos_id = eos  # -1 = never stop early (deterministic lengths)
    streams = [batcher.submit_ids(p, max_new_tokens=max_new) for p in ps]
    return streams, [s.result(timeout=timeout) for s in streams]


@pytest.fixture(scope="module")
def ref_outs(spair, tok):
    """Primary-only greedy outputs for the module's canonical prompts —
    the parity oracle every speculative storm is compared against."""
    eng, _ = spair
    b = DecodeBatcher(eng, max_waiting=16).start()
    _, outs = run_streams(b, prompts())
    b.stop()
    return outs


# ------------------------------------------------------ parity + acceptance

def test_speculative_bitwise_parity(spair, ref_outs):
    """THE speculation pin: draft-k/verify-1 emits bitwise the tokens
    primary-only decode emits, with zero post-warmup retraces on BOTH
    engines and zero leaked pages after drain."""
    eng, dr = spair
    b = spec_batcher(spair)
    r0 = eng.metrics.retraces.value + dr.metrics.retraces.value
    m0 = eng.metrics.cache_misses.value + dr.metrics.cache_misses.value
    _, outs = run_streams(b, prompts())
    snap = b.spec_snapshot()
    b.stop()
    assert outs == ref_outs
    assert eng.metrics.retraces.value + dr.metrics.retraces.value == r0
    assert eng.metrics.cache_misses.value \
        + dr.metrics.cache_misses.value == m0
    # identical weights on both sides: the ceiling case — near-total
    # acceptance, and the accounting sees real draft/accept volume
    assert snap["enabled"] and snap["draft_k"] == DRAFT_K
    assert snap["rounds"] > 0 and snap["draft_tokens"] > 0
    assert snap["accept_rate"] > 0.9
    assert set(snap["by_model"]) == {"bert-tiny", "bert-tiny-draft"}
    for e in (eng, dr):
        lk = e.leak_check()
        assert lk["ok"] and not lk["stream_owners"], lk


def test_drafter_kill_mid_storm_degrades(spair, ref_outs):
    """Chaos: the drafter dies mid-storm — the pair degrades to
    primary-only decode (no stall, no stream loss) and the output stays
    bitwise identical; the drafter's pages all come home."""
    eng, dr = spair
    b = spec_batcher(spair)
    b.eos_id = -1
    streams = [b.submit_ids(p, max_new_tokens=8) for p in prompts()]
    b.kill_drafter(RuntimeError("chaos: drafter OOM"))
    outs = [s.result(timeout=120) for s in streams]
    deaths = b.metrics.drafter_deaths_total.value
    b.stop()
    assert outs == ref_outs
    assert b.drafter is None  # degraded, not stalled
    assert deaths >= 1
    lk = dr.leak_check()
    assert lk["ok"] and not lk["stream_owners"], lk
    # the forced degrade is decision-recorded with a complete chain
    rep = validate_decisions(eng.tracer.records())
    assert rep["incomplete"] == {}
    assert rep["by_knob"].get("draft_k", 0) >= 1


def test_set_draft_k_clamps_pause_resume(spair, ref_outs):
    """``set_draft_k`` clamps to [0, DRAFT_K_MAX]; k=0 pauses
    speculation (primary-only rounds, parity intact) and a later resume
    speculates again — the serve-loop knob the controller actuates."""
    eng, dr = spair
    b = spec_batcher(spair)
    b.set_draft_k(99)
    assert b.draft_k == b.DRAFT_K_MAX
    b.set_draft_k(-3)
    assert b.draft_k == 0
    rounds0 = b.spec_snapshot()["rounds"]
    _, outs = run_streams(b, prompts())
    assert outs == ref_outs
    assert b.spec_snapshot()["rounds"] == rounds0  # paused: no drafting
    b.set_draft_k(DRAFT_K)
    _, outs = run_streams(b, prompts())
    assert outs == ref_outs
    assert b.spec_snapshot()["rounds"] > rounds0  # resumed
    b.stop()


# ------------------------------------------------------- hop-chain contract

def test_draft_verify_chains_round_trip(spair, tok):
    """Every speculated stream's chain validates end to end: draft hops
    carry k/drafter_model, verify hops carry matched<=k and a monotone
    cumulative ``accepted``, and ``validate_chains`` reports the
    speculated count + acceptance."""
    eng, dr = spair
    b = spec_batcher(spair)
    streams, _ = run_streams(b, prompts(n=4, seed=11))
    b.stop()
    rids = [s.rid for s in streams]
    records = eng.tracer.records()
    report = validate_chains(records, rids)
    assert report["incomplete"] == {}
    assert report["complete"] == len(rids)
    assert report["speculated"] == len(rids)
    assert report["accept_rate"] is not None
    hops = [r.get("attrs") or {} for r in records
            if (r.get("attrs") or {}).get("request_id") in set(rids)]
    drafts = [a for a in hops if a.get("hop") == "draft"]
    verifies = [a for a in hops if a.get("hop") == "verify"]
    assert drafts and len(drafts) == len(verifies)
    for a in drafts:
        assert a["k"] == DRAFT_K
        assert a["drafter_model"] == "bert-tiny"
    for a in verifies:
        assert 0 <= a["matched"] <= a["k"]
        assert a["accepted"] >= a["matched"]


def H(hop, **kw):
    return {"attrs": {"hop": hop, **kw}}


def test_chain_rules_catch_spec_violations():
    """The speculation chain rules fire on synthetic violations and stay
    silent on the legal shape."""
    ok = [H("admit"), H("prefill"),
          H("draft", k=4), H("verify", k=4, matched=2, accepted=2),
          H("draft", k=4), H("verify", k=4, matched=4, accepted=6),
          H("complete")]
    assert chain_issues(ok) == []
    # a verification with no drafted window
    bad = [H("admit"), H("prefill"), H("verify", accepted=1),
           H("complete")]
    assert any("not immediately preceded" in i for i in chain_issues(bad))
    # a drafted window nobody verified
    bad = [H("admit"), H("prefill"), H("draft", k=4), H("complete")]
    assert any("not immediately followed" in i for i in chain_issues(bad))
    # drafting from a cache no prefill filled
    bad = [H("admit"), H("draft", k=4), H("verify", accepted=1),
           H("complete")]
    assert any("no earlier 'prefill'" in i for i in chain_issues(bad))
    # cumulative acceptance running backwards
    bad = [H("admit"), H("prefill"),
           H("draft", k=4), H("verify", accepted=4),
           H("draft", k=4), H("verify", accepted=2), H("complete")]
    assert any("monotone" in i for i in chain_issues(bad))


# -------------------------------------------------- controller speculation law

class FakeSpecRouter:
    """Router-shaped double exposing exactly what the speculation law
    consumes: a ``draft_k`` knob and cumulative draft/accept counters
    the test scripts per tick."""

    def __init__(self, k=6):
        self.knobs = {"draft_k": k}
        self.drafted = 0
        self.accepted = 0
        self.applied = []
        self.tracer = Tracer(enabled=True)

    def feed(self, rate, n=1000):
        self.drafted += n
        self.accepted += int(n * rate)

    def knob_values(self):
        return dict(self.knobs)

    def apply_knob(self, name, value):
        if name != "draft_k":
            raise KeyError(name)
        self.knobs[name] = value
        self.applied.append((name, value))

    def control_snapshot(self):
        return {
            "router": {"requests_total": 0, "deadline_expired_total": 0,
                       "queue_depth": 0.0, "admission": {}},
            "active": 1, "standby": 0,
            "knobs": dict(self.knobs),
            "speculation": {"draft_tokens": self.drafted,
                            "accepted_tokens": self.accepted},
        }


def _spec_controller(k=6, **kw):
    r = FakeSpecRouter(k=k)
    clk = FakeClock()
    kw.setdefault("eval_window_s", 5.0)
    c = ServeController(r, clock=clk, tracer=r.tracer, **kw)
    assert c.step() is None  # first tick only primes the counter deltas
    clk.advance(1.0)
    return c, r, clk


def _tick(c, r, clk, rate=None, dt=1.0):
    if rate is not None:
        r.feed(rate)
    s = c.step()
    clk.advance(dt)
    return s


def test_law_halves_then_disables_on_low_acceptance():
    """Acceptance below the floor for ``spec_patience`` ticks halves k;
    catastrophic acceptance (< floor/2) switches speculation off — and
    every decision chain closes."""
    c, r, clk = _spec_controller(k=6)
    _tick(c, r, clk, rate=0.20)
    assert r.knobs["draft_k"] == 6  # one low tick is not a verdict
    _tick(c, r, clk, rate=0.20)
    assert r.knobs["draft_k"] == 3
    clk.advance(6.0)  # clear the knob cooldown
    _tick(c, r, clk, rate=0.20)
    _tick(c, r, clk, rate=0.20)
    assert r.knobs["draft_k"] == 1
    clk.advance(6.0)
    _tick(c, r, clk, rate=0.10)  # < floor/2: catastrophic
    _tick(c, r, clk, rate=0.10)
    assert r.knobs["draft_k"] == 0
    c.stop()
    rep = validate_decisions(r.tracer.records())
    assert rep["incomplete"] == {}
    assert rep["by_knob"].get("draft_k", 0) >= 3


def test_law_deepens_on_high_acceptance_capped():
    """Acceptance above the high band steps k up by one per cooldown,
    clamped to the spec's ceiling."""
    c, r, clk = _spec_controller(k=6)
    _tick(c, r, clk, rate=0.95)
    assert r.knobs["draft_k"] == 7
    _tick(c, r, clk, rate=0.95)  # cooldown holds: no double-step
    assert r.knobs["draft_k"] == 7
    clk.advance(6.0)
    _tick(c, r, clk, rate=0.95)
    assert r.knobs["draft_k"] == 8
    clk.advance(6.0)
    _tick(c, r, clk, rate=0.95)  # at the ceiling: the law stands still
    assert r.knobs["draft_k"] == 8
    c.stop()
    assert validate_decisions(r.tracer.records())["incomplete"] == {}


def test_law_dormant_without_drafting():
    """No drafting in the window (accept_rate None) or speculation off
    (k=0) ticks the law to a standstill — no blind retries."""
    c, r, clk = _spec_controller(k=6)
    for _ in range(4):
        _tick(c, r, clk)  # no feed: accept_rate is None
    assert r.applied == []
    c2, r2, clk2 = _spec_controller(k=0)
    for _ in range(4):
        _tick(c2, r2, clk2, rate=0.10)  # counters move, but k=0
    assert r2.applied == []
    c.stop()
    c2.stop()


def test_law_auto_reverts_regressing_reenable():
    """A forced re-enable (inject) whose ``spec_waste`` regresses past
    the margin auto-reverts at the evaluation window, with the revert
    chained to the decision it undoes."""
    c, r, clk = _spec_controller(k=0)
    _tick(c, r, clk, rate=0.90)  # baseline sense: spec_waste 0.1
    assert c.inject("draft_k", 6, "test revert probe")
    assert r.knobs["draft_k"] == 6
    for _ in range(8):  # mid-band rate: law silent, waste regresses
        _tick(c, r, clk, rate=0.50)
    assert r.knobs["draft_k"] == 0
    assert c.reverts_total >= 1
    c.stop()
    rep = validate_decisions(r.tracer.records())
    assert rep["incomplete"] == {}
    assert rep["reverted"] >= 1


# --------------------------------------------------- router/exporter surface

def test_router_spec_knob_and_exporter_labels(spair):
    """The router's controller quack (``draft_k`` only when a pair
    speculates), the /healthz block, and the per-model Prometheus labels
    the exporter renders from ``by_model``."""
    eng, dr = spair
    router = DecodeRouter([eng], drafters=[dr], draft_k=DRAFT_K)
    assert router.knob_values() == {"draft_k": DRAFT_K}
    router.apply_knob("draft_k", 2)
    assert router.batchers[0].draft_k == 2
    with pytest.raises(ValueError):
        router.apply_knob("hedge_ms", 1.0)
    router.apply_knob("draft_k", DRAFT_K)
    hs = router.health_summary()
    assert hs["speculating"] == 1 and hs["draft_k"] == DRAFT_K
    assert {"alive", "replicas", "accept_rate",
            "drafter_deaths"} <= set(hs)
    snap = router.control_snapshot()
    assert "by_model" in snap["speculation"]
    text = "\n".join(prometheus_lines("decode", snap))
    assert 'model="bert-tiny-draft"' in text
    # a plain pool exposes NO draft_k: the speculation law stays dormant
    plain = DecodeRouter([eng])
    assert plain.knob_values() == {}


def test_batcher_rejects_bad_drafter_pairings(spair, tok):
    """Ctor validation: a prefix-sharing drafter is refused (its cold
    prefill rewrites pages in place), and so is one whose geometry does not
    line up with the primary's slots and positions."""
    eng, _ = spair
    with pytest.raises(ValueError, match="prefix_share"):
        DecodeBatcher(eng, drafter=eng)  # primary shares prefixes
    short = PagedDecodeEngine(make_args(decode_max_len=eng.max_len // 2),
                              tokenizer=tok, mesh=None, buckets=BUCKETS,
                              prefix_share=False)
    with pytest.raises(ValueError, match="geometry"):
        DecodeBatcher(eng, drafter=short)
