"""Shared driver of the two decode traffic kinds: one
``PagedDecodeEngine`` behind one ``DecodeBatcher`` (as ``serve_tpu.py
--decode`` builds them), requests through ``DecodeBatcher.submit_ids``.

ONE load thread (this one) submits on schedule and watches every live
stream's ``emitted`` list about once a millisecond; a token's time is the
time this thread saw it, on this thread's clock.  Nothing else runs beside
the batcher's own worker.

- open loop: requests are due at times fixed before the run; time to first
  token runs from the DUE time; the generator's lateness is reported.
- closed loop: as many clients as the traffic says, each sending its next
  request when it sees the last one finished.  The window opens and closes
  on an observed burst of tokens (whole decode steps), and the rate is the
  tokens seen between the two bursts over the time between them.
"""
from __future__ import annotations

import collections
import gc
import json
import os
import random
import sys
import time

from benchmark import adapters, common, loadgen, spans

POLL_S = 0.001
DRAIN_S = 30.0
REF_ROWS = 8      # requests the reference runs over in one call


class Live:
    __slots__ = ("req", "stream", "due", "seen", "last_at", "first_at",
                 "prompt_len", "client", "slot", "gaps")

    def __init__(self, req, stream, due, client=None):
        self.req, self.stream, self.due = req, stream, due
        self.seen, self.last_at, self.first_at = 0, None, None
        self.prompt_len, self.client, self.slot = len(req.prompt), client, None
        self.gaps = []


def build(cell, ctx, sizes, wd):
    """The engine and its batcher, warmed: every program the traffic's
    buckets can reach is compiled before this returns."""
    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, load_vocab
    from pdnlp_tpu.serve.decode import DecodeBatcher, PagedDecodeEngine
    from pdnlp_tpu.utils.config import Args

    from benchmark.reference import weights

    prog = dict(cell.config["program"])
    eng = dict(cell.config["assumed"])
    if ctx.rehearse:
        prog.update(cell.rehearsal("program"))
        eng.update(cell.rehearsal("assumed"))
    vocab = os.path.join(wd, "vocab.txt")
    with open(vocab, "w", encoding="utf-8") as f:
        f.write("\n".join(loadgen.vocab_lines(sizes["vocab_size"])) + "\n")
    args = Args(vocab_path=vocab, output_dir=wd, data_path=vocab,
                max_seq_len=eng["max_len"], decode_max_len=eng["max_len"],
                decode_slots=eng["slots"], kv_page_sz=eng["page_size"],
                kv_hbm_mb=eng.get("kv_hbm_mb", 0.0), kv_layout="paged",
                seed=ctx.seed % (2 ** 31 - 1), **prog)
    tok = WordPieceTokenizer(load_vocab(vocab))
    engine = PagedDecodeEngine(
        args, tokenizer=tok, buckets=tuple(cell.traffic["buckets"]),
        prefill_rows=eng.get("prefill_rows"))
    banned = (tok.sep_id,)
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (engine.params, engine.head))
    engine.params = engine.head = None     # never two sets of weights
    tree = weights.make_weights(
        ctx.seed, sizes, banned=banned,
        layout=lambda w: (adapters.to_program_params(w),
                          adapters.to_program_head(w)))
    adapters.check_same_tree(tree[0], like[0], "parameter")
    adapters.check_same_tree(tree[1], like[1], "LM head")
    engine.params, engine.head = tree
    # every request names its own ``max_new``; the default is never used
    batcher = DecodeBatcher(engine, max_waiting=eng["max_waiting"],
                            default_max_new=64)
    batcher.start()
    batcher.warmup()
    jax.block_until_ready((engine._cache_k, engine._cache_v))
    return engine, batcher, banned


class Counters:
    """The program's counters this kind reads, as one flat dict."""

    def __init__(self, engine, batcher):
        self.e, self.b = engine, batcher

    def read(self) -> dict:
        m, r = self.b.metrics, self.b.rmetrics
        p = self.e.prefix.snapshot()
        return {
            "decode_steps": m.decode_steps_total.value,
            "prefills": m.prefills_total.value,
            "prefill_tokens": m.prefill_tokens_total.value,
            "tokens_out": m.tokens_out_total.value,
            "rejected": m.rejected_total.value,
            "expired": m.deadline_expired_total.value,
            "occupancy_sum": r.slot_occupancy.total,
            "occupancy_n": r.slot_occupancy.count,
            "prefix_full": p["hits_full"], "prefix_partial": p["hits_partial"],
            "prefix_miss": p["misses"],
            "retraces": self.e.metrics.retraces.value,
        }


def run(cell, ctx, closed: bool) -> dict:
    import jax

    sizes = cell.sizes(ctx.rehearse)
    tr = dict(cell.traffic)
    if ctx.rehearse:
        tr.update(cell.rehearsal("traffic"))
        cell.traffic = tr
    wd = common.work_dir(cell.name)
    engine, batcher, banned = build(cell, ctx, sizes, wd)
    eng_slots, max_len = engine.slots, engine.max_len
    n_pages = engine.n_pages
    seconds = min(ctx.seconds, tr["trace_seconds"]) if ctx.trace else ctx.seconds
    ramp = tr["ramp_s"]
    horizon = ramp + seconds
    V = sizes["vocab_size"]
    if closed:
        source = loadgen.closed_loop_prompts(tr, ctx.seed, V)
        clients = tr["clients"]
        requests = None
    else:
        requests = loadgen.open_loop_sessions(tr, ctx.seed, V, horizon, max_len)
    counters = Counters(engine, batcher)
    live, finished = [], []
    failed = attempted = 0
    lateness, ttft, itl = [], [], []
    bursts_at = []     # when, inside the window, new tokens were seen
    marks = []         # closed loop: the counts at the window's thirds
    state = {"open": None, "close": None, "tokens": 0, "c0": None, "c1": None,
             "kv_sum": 0.0, "pages_sum": 0.0, "bursts": 0, "last_steps": 0}
    gc.collect()
    gc.freeze()
    note = ctx.annotate
    t0 = common.now()
    w_open, w_close = t0 + ramp, t0 + horizon

    def no_first_token(due):
        """A request due in the window that never got its first token waited
        the whole drain: the tail is the tail of all requests."""
        if not closed and w_open <= due < w_close:
            ttft.append(DRAIN_S * 1e3)

    def submit(req, due, client=None):
        nonlocal failed, attempted
        attempted += 1
        try:
            s = batcher.submit_ids(req.prompt, max_new_tokens=req.max_new)
        except Exception as e:  # noqa: BLE001 — a refusal is a failed request
            failed += 1
            no_first_token(due)
            print(f"benchmark: refused: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return None
        lv = Live(req, s, due, client)
        live.append(lv)
        return lv

    def poll(now):
        nonlocal failed
        new_tokens = 0
        done = []
        for lv in live:
            n = len(lv.stream.emitted)
            if n > lv.seen:
                if lv.first_at is None:
                    lv.first_at, lv.slot = now, getattr(lv.stream, "slot", None)
                    if w_open <= lv.due < w_close:
                        ttft.append((now - lv.due) * 1e3)
                elif w_open <= now < w_close or closed:
                    itl.append((now - lv.last_at) * 1e3)
                    lv.gaps.append(itl[-1])
                new_tokens += n - lv.seen
                lv.seen, lv.last_at = n, now
            if lv.stream.done() and len(lv.stream.emitted) == lv.seen:
                done.append(lv)
        for lv in done:
            live.remove(lv)
            if lv.seen == 0 or not ended_well(lv.stream):
                failed += 1
                if lv.first_at is None:
                    no_first_token(lv.due)
            else:
                finished.append(lv)
        return new_tokens, done

    def mark(now):
        marks.append((now, state["tokens"], attempted,
                      batcher.metrics.prefills_total.value))

    tracing = None
    i = 0
    setup_s = None
    if closed:
        # first wave: staggered lengths, so that streams do not end together
        for c in range(clients):
            prompt, new = next(source)
            first_new = max(1, round(new * (c + 1) / clients))
            submit(loadgen.Request(0.0, prompt, first_new, c, 0), t0, c)
    while True:
        now = common.now()
        if not closed:
            while i < len(requests) and t0 + requests[i].due <= now:
                due = t0 + requests[i].due
                with note("submit"):
                    lv = submit(requests[i], due)
                if w_open <= due < w_close:
                    lateness.append((common.now() - due) * 1e3)
                i += 1
        new_tokens, done = poll(now)
        if closed:
            for lv in done:
                prompt, new = next(source)
                submit(loadgen.Request(0.0, prompt, new, lv.client, 0), now,
                       lv.client)
        # ---- window edges
        if tracing is None and ctx.trace and now >= w_open - tr["trace_lead_s"]:
            # stalls this thread: before the window
            tracing = ctx.start_trace(python_tracer=False)
            continue
        if state["open"] is None and now >= w_open and (new_tokens or not closed):
            state["open"] = now
            state["c0"] = counters.read()
            state["last_steps"] = state["c0"]["decode_steps"]
            setup_s = common.process_age_s()
            w_open, w_close = (now, now + seconds) if closed else (w_open, w_close)
            if closed:
                mark(now)
        elif state["open"] is not None and state["close"] is None:
            if new_tokens:
                state["tokens"] += new_tokens
                bursts_at.append(now)
                steps_now = batcher.metrics.decode_steps_total.value
                d = steps_now - state["last_steps"]
                if d > 0:
                    state["last_steps"] = steps_now
                    state["bursts"] += d
                    state["kv_sum"] += d * sum(l.prompt_len + l.seen
                                               for l in live if l.seen)
                    state["pages_sum"] += d * engine.allocator.used_pages
                if closed and len(marks) < 3 \
                        and now >= w_open + len(marks) * seconds / 3.0:
                    mark(now)
            if now >= w_close and (new_tokens or not closed):
                state["close"] = now
                state["c1"] = counters.read()
                if closed:
                    mark(now)
        if state["close"] is not None:
            waiting = [l for l in live if l.first_at is None
                       and w_open <= l.due < w_close]
            if closed or not waiting or now > w_close + DRAIN_S:
                for lv in () if closed else waiting:
                    failed += 1
                    no_first_token(lv.due)
                break
        if closed:
            time.sleep(POLL_S)
        else:
            nxt = t0 + requests[i].due if i < len(requests) else now + POLL_S
            time.sleep(max(0.0, min(POLL_S, nxt - now)))
    window = state["close"] - state["open"]
    trace = ctx.stop_trace(tracing)
    gc.unfreeze()
    peak = common.memory_peak_bytes(ctx.devices)
    batcher.stop(drain=False)
    retraced = state["c1"]["retraces"] - state["c0"]["retraces"]
    sample = pick_sample(finished, ctx.seed, tr["check_requests"])
    served = [(list(lv.req.prompt), list(lv.stream.emitted)) for lv in sample]
    n_finished = len(finished)
    if os.environ.get("BENCHMARK_KEEP_SAMPLES"):
        # a builder's look at the whole sample (README): never read back
        with open(os.path.join(wd, f"samples_{ctx.seed}.json"), "w") as f:
            json.dump({"ttft_ms": ttft, "itl_ms": itl, "window_s": window,
                       "requests": [[lv.prompt_len, lv.seen, lv.slot, lv.gaps]
                                    for lv in finished]}, f)
    del engine, batcher, counters, live, finished, sample
    gc.collect()
    limits = dict(cell.config["check"])
    if ctx.rehearse:
        limits.update(cell.rehearsal("check"))
    checks = compare(served, ctx.seed, sizes, banned, limits)
    checks.add("compiled_in_window", float(retraced), 0.0,
               "programs traced after the window opened")
    checks.emit()
    c0, c1 = state["c0"], state["c1"]
    delta = {k: c1[k] - c0[k] for k in c0}
    obs = {
        "counters": {
            **delta, "window_s": window, "slots": eng_slots,
            "n_pages": n_pages, "tokens_seen": state["tokens"],
            "live_rows_sum": delta["occupancy_sum"] * eng_slots,
            "slot_steps": delta["occupancy_n"] * eng_slots,
            "live_kv_tokens_sum": state["kv_sum"],
            "pages_live_sum": state["pages_sum"], "bursts": state["bursts"],
            "pages_steps": state["bursts"] * n_pages,
            "prefix_lookups": delta["prefix_full"] + delta["prefix_partial"]
            + delta["prefix_miss"],
            "memory_peak_bytes": peak, "finished": n_finished,
        },
        "samples": {"lateness_ms": lateness, "ttft_ms": ttft, "itl_ms": itl},
        "trace": trace, "sizes": sizes, "peaks": ctx.peaks,
        "thirds": thirds(marks),
    }
    gaps = sorted((b - a) * 1e3 for a, b in zip(bursts_at, bursts_at[1:]))
    common.say({"burst_gap_ms": {"p50": common.percentile(gaps, 50),
                                 "largest": [round(g, 1) for g in gaps[-5:]]}
                if gaps else {},
                "window_s": window, "requests_due_in_window": len(ttft),
                "itl_samples": len(itl), "finished": n_finished,
                "tokens_seen": state["tokens"],
                "lateness_p99_ms": common.percentile(lateness, 99) if lateness else None,
                **({"thirds": obs["thirds"]} if closed else {}),
                **({"decode_steps_by_rows": steps_by_rows(obs)} if ctx.trace else {})})
    # a latency metric is defined by its name: <sample>_p<N>_ms
    e2e = {"setup_s": setup_s, "decode_tokens_per_s": state["tokens"] / window}
    latency = {"ttft": ttft, "itl": itl}
    for m in cell.end_to_end():
        v = common.latency_metric(m["name"], latency)
        if v is not None:
            e2e[m["name"]] = v
    return {"checks": checks.rows, "correct": checks.correct, "attempted": attempted,
            "failed": failed, "end_to_end": e2e, "obs": obs,
            "memory_peak_bytes": peak}


def thirds(marks) -> list:
    """Per third of a closed loop's window, from the counts marked at its
    edges ``(seconds, tokens seen, requests sent, prefill launches)``:
    tokens a second, and rows a prefill launch (requests sent in the third
    over the launches that seated them).  A window whose thirds agree lies
    in a steady state; one that climbs lies in the mix's transient."""
    out = []
    for (t0, k0, r0, p0), (t1, k1, r1, p1) in zip(marks, marks[1:]):
        out.append({"tokens_per_s": (k1 - k0) / (t1 - t0),
                    "rows_per_prefill": (r1 - r0) / (p1 - p0)
                    if p1 > p0 else None})
    return out


def steps_by_rows(obs) -> dict:
    """Traced runs: the decode steps of the traced window by the rows their
    launch computed (the ``rows`` of the program's ``decode.dispatch``
    leaves; a program without the attribute gives nothing)."""
    rows = ((r.get("attrs") or {}).get("rows") for r in spans.records(obs)
            if r.get("name") == spans.STEP)
    return dict(collections.Counter(str(n) for n in rows if n is not None))


def ended_well(stream) -> bool:
    try:
        stream.result(timeout=0.0)
        return True
    except Exception:  # noqa: BLE001 — any error ends the request as failed
        return False


def pick_sample(finished, seed, k):
    """``k`` finished requests drawn from the seed: the longest, then in a
    seeded order those served by a slot the sample does not hold yet, then
    the others — so that a fault confined to a few slots is met."""
    if not finished:
        return []
    longest = max(finished, key=lambda lv: lv.prompt_len + lv.seen)
    rest = [lv for lv in finished if lv is not longest]
    random.Random(seed).shuffle(rest)
    slots, fresh, again = {longest.slot}, [], []
    for lv in rest:
        (again if lv.slot in slots else fresh).append(lv)
        slots.add(lv.slot)
    return ([longest] + fresh + again)[:k]


def compare(served, seed, sizes, banned, limits, prec="f32") -> common.Checks:
    """Run the reference once over each sampled prompt with its served
    tokens: the widest gap by which a served token's logit lies below the
    reference's best at its position.  (Greedy decoding: every served token
    should BE the reference's best, up to the rounding of bf16.)"""
    checks = common.Checks()
    gaps = reference_gaps(served, seed, sizes, banned, prec)
    n = sum(len(g) for g in gaps)
    worst = max((max(g) for g in gaps if g), default=float("nan"))
    checks.add("served_logit_gap", worst, limits["served_logit_gap"],
               f"{n} served tokens of {len(served)} requests against the "
               "float32 reference")
    checks.add("served_tokens_compared", float(n),
               float(limits["min_served_tokens"]),
               "served tokens the sample held", at_least=True)
    return checks


def reference_gaps(served, seed, sizes, banned, prec="f32", lowprec=None):
    """Per request, per served token: reference best logit minus the
    logit of the served token.  With ``lowprec``, the token judged at each
    position is instead the one the reference computed in that precision
    puts first (the control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import model, weights

    w = weights.make_weights(seed, sizes, banned=banned)
    heads, eps = sizes["num_attention_heads"], sizes["layer_norm_eps"]

    @jax.jit
    def gaps_of(w, ids, mask, served_next):
        logits = model.causal_logits(w, ids, mask, heads=heads, eps=eps,
                                     prec=prec)
        if lowprec is not None:
            low = model.causal_logits(w, ids, mask, heads=heads, eps=eps,
                                      prec=lowprec)
            served_next = jnp.argmax(low, axis=-1)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, served_next[..., None], axis=-1)[..., 0]
        return best - got

    out = []
    width = sizes["max_position_embeddings"]
    for at in range(0, len(served), REF_ROWS):
        rows = served[at:at + REF_ROWS]
        ids = np.zeros((REF_ROWS, width), np.int32)
        nxt = np.zeros((REF_ROWS, width), np.int32)
        mask = np.zeros((REF_ROWS, width), np.int32)
        mask[len(rows):, 0] = 1             # filler rows: one token, unread
        for r, (prompt, emitted) in enumerate(rows):
            seq = (prompt + emitted)[:width]
            T = len(seq)
            ids[r, :T], mask[r, :T], nxt[r, : T - 1] = seq, 1, seq[1:]
        g = np.asarray(gaps_of(w, jnp.asarray(ids), jnp.asarray(mask),
                               jnp.asarray(nxt)))
        for r, (prompt, emitted) in enumerate(rows):
            T = min(len(prompt) + len(emitted), width)
            # positions that predict served tokens
            out.append([float(x) for x in g[r, len(prompt) - 1:T - 1]])
    return out
