"""Traffic kind ``closed_loop_latent_moe``: the closed loop of
``closed_loop_saturating`` (as many clients as the traffic says, each
sending its next distinct prompt when the last one finished) driving a
latent-attention, sparse-expert decoder through the SAME
``PagedDecodeEngine`` / ``DecodeBatcher`` as the BERT causal LM.

What ``kinds/serve.py`` has is used from there (``Live``, ``Counters``,
``pick_sample``, ``ended_well``, the generator
``loadgen.closed_loop_prompts``); what is BERT's there is stated again here:

- ``build``: the weights come from ``reference/axk1.py``, a layer at a time,
  laid out as the program's trees BEFORE the engine and its page pool
  exist, so that neither a second set nor a float32 copy is ever alive
  beside the pool;
- ``compare``: the reference runs the sampled requests padded to ONE length
  (one compiled program a layer kind), a layer's weights at a time, and the
  comparison knows near-tie routing (below);
- the per-layer numbers no fixed reducer can compute are computed here and
  placed in ``obs["counters"]``, where ``counter`` / ``ratio`` metric files
  read them: the rooflines' least seconds (``counts_axk1``) beside the
  programs' device seconds, and the sums of the leaves' attributes (cache
  positions read and live, assignments to held experts), and the four
  host numbers of the leaves whose readers for the BERT cells are ``.py``
  files (held at ten, one cell each).  Everything else per layer is read
  by the ``.json`` metric files the saturated BERT cell has: they take the
  same ``Counters`` and trace.

The loop differs from ``closed_loop_saturating`` in one thing: a cycle's
prompts come in a LEVELLED order (``levelled_prompts``), because this cell's
window holds less than one cycle.  Every run prints ``round_ms``, the
worker's rounds as the poll saw them.

``correct``: the widest gap by which a served token's logit lies below the
reference's best, over the served positions whose routing is not a near tie
(``served_logit_gap``).  A position is a NEAR TIE where the reference's
routing margin (``reference/axk1.py``) is under ``swap_margin``: there the
program, in bfloat16, may take the other expert or group, and its hidden
state then differs by a whole expert.  Such a swap is allowed only there;
the swaps are counted — near-tie positions whose gap exceeds ``swap_gap`` —
and their share of all served positions is limited
(``routing_swap_share``).  The limit on the gap is never widened for them:
outside near ties every position is held to it, and a fault confined to
near ties shows as swaps past their share.
"""
from __future__ import annotations

import functools
import gc
import os
import random
import sys
import time

from benchmark import common, counts_axk1, loadgen, spans
from benchmark.kinds.serve import (POLL_S, Counters, Live, ended_well,
                                   pick_sample)

REF_PAD = 512      # sampled requests are padded to one multiple of this


def model_sizes(cell, rehearse: bool) -> dict:
    """The configuration's numbers plus its ``rope_scaling`` group."""
    sizes = cell.sizes(rehearse)
    sizes["rope_scaling"] = dict(cell.config["rope_scaling"])
    return sizes


def make_weights(like, seed: int, sizes: dict, banned: tuple):
    """The benchmark's seeded weights as the program's trees ``like``
    (shapes): every stacked leaf is filled a layer at a time through a
    donated update, so the peak is the model plus one layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import axk1

    key = axk1.seed_key(seed)
    top = jax.jit(lambda k: axk1.top_weights(k, sizes, banned))(key)
    dense_n = int(sizes.get("first_k_dense_replace", 1))

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
    def fill(stack, k, l, at):
        new = axk1.layer_weights(k, sizes, l)
        return jax.tree_util.tree_map(lambda s, x: s.at[at].set(x), stack, new)

    def stacked(shapes, first):
        stack = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                       shapes)
        n = jax.tree_util.tree_leaves(shapes)[0].shape[0]
        for i in range(n):
            stack = fill(stack, key, first + i, i)
        return stack

    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "dense": stacked(like[0]["dense"], 0),
              "moe": stacked(like[0]["moe"], dense_n)}
    head = {"kernel": top["head"]}
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, head))
    if got != like:
        raise SystemExit(
            "benchmark: the program's parameter tree is not the one "
            f"reference/axk1.py lays out\n  benchmark: {got}\n  program:   {like}")
    return params, head


def levelled_prompts(traffic: dict, seed: int, vocab_size: int):
    """``loadgen.closed_loop_prompts`` — an endless stream of (prompt,
    max_new) whose every ``cycle`` requests hold the same multiset of lengths
    in a seeded order — with that order LEVELLED: the multiset is cut into
    ``strata`` equal parts by length, and every ``strata`` consecutive
    requests hold one length of each part, in a seeded order of their own.

    Why: a prompt's prefill costs by its bucket (76.8 ms at 2 048, 128.4 at
    3 072 on the chip), a 30 s window of this cell holds about 93 requests of
    a cycle's 128, and which 93 a plain shuffle puts there moves tokens/s by
    0.2 % for each long prompt more or less: the whole of what a calm run
    differs by from another (PERF.md section 6).  The permutation of one
    multiset is there to make runs comparable; it does that only where a
    window holds whole cycles.  Levelled, any window holds the deployment's
    mix to within a prompt or two."""
    cycle = traffic["cycle"]
    strata = min(traffic["strata"], cycle)
    if cycle % strata:
        raise SystemExit(f"benchmark: a cycle of {cycle} requests cannot be "
                         f"cut into {strata} equal parts")
    rng = random.Random(seed)
    lengths = sorted(int(round(x)) for x in
                     loadgen.quantiles(traffic["prompt_tokens"], cycle))
    per = cycle // strata
    while True:
        parts = [lengths[i * per:(i + 1) * per] for i in range(strata)]
        for part in parts:
            rng.shuffle(part)
        for j in range(per):
            dealt = [part[j] for part in parts]
            rng.shuffle(dealt)
            for n in dealt:
                yield (loadgen.token_ids(rng, n, vocab_size),
                       traffic["new_tokens"])


def build(cell, ctx, sizes, wd):
    """The engine and its batcher, warmed: every program the traffic's
    buckets can reach is compiled before this returns.  The weights are made
    BEFORE the engine and its page pool exist, and the engine is given
    them: it makes none of its own (the family's are made on first read)."""
    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, load_vocab
    from pdnlp_tpu.models import families, get_config
    from pdnlp_tpu.serve.decode import DecodeBatcher, PagedDecodeEngine
    from pdnlp_tpu.utils.config import Args

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
    banned = (tok.sep_id,)
    cfg = get_config(args.model, vocab_size=tok.vocab_size)
    family = families.of(cfg)
    key = jax.random.key(0)
    like = jax.eval_shape(lambda: (family.init_params(key, cfg),
                                   family.init_head(key, cfg)))
    t = common.now()
    weights = make_weights(like, ctx.seed, sizes, banned)
    jax.block_until_ready(weights)
    made_s = common.now() - t
    engine = PagedDecodeEngine(
        args, tokenizer=tok, buckets=tuple(cell.traffic["buckets"]),
        prefill_rows=eng.get("prefill_rows"))
    if engine.n_pages != eng["pool_pages"]:
        raise SystemExit(f"benchmark: the engine's pool holds {engine.n_pages} "
                         f"pages, the configuration says {eng['pool_pages']}")
    engine.params, engine.head = weights
    del weights
    batcher = DecodeBatcher(engine, max_waiting=eng["max_waiting"],
                            default_max_new=cell.traffic["new_tokens"])
    batcher.start()
    t = common.now()
    batcher.warmup()
    jax.block_until_ready(engine._pools)
    common.say({"weights_s": made_s, "warmup_s": common.now() - t, "kv": {
        k: v for k, v in engine.kv_snapshot().items()
        if k in ("cache_bytes", "kv_pool_bytes", "weights_bytes")}})
    return engine, batcher, banned


def run(cell, ctx) -> dict:
    sizes = model_sizes(cell, ctx.rehearse)
    tr = dict(cell.traffic)
    if ctx.rehearse:
        tr.update(cell.rehearsal("traffic"))
        cell.traffic = tr
    wd = common.work_dir(cell.name)
    engine, batcher, banned = build(cell, ctx, sizes, wd)
    eng_slots, n_pages = engine.slots, engine.n_pages
    seconds = min(ctx.seconds, tr["trace_seconds"]) if ctx.trace else ctx.seconds
    source = levelled_prompts(tr, ctx.seed, sizes["vocab_size"])
    clients = tr["clients"]
    counters = Counters(engine, batcher)
    live, finished = [], []
    failed = attempted = 0
    state = {"open": None, "close": None, "tokens": 0, "c0": None, "c1": None,
             "kv_sum": 0.0, "pages_sum": 0.0, "bursts": 0, "last_steps": 0}
    gaps = []      # seconds a decode step, as the poll saw the counter move
    gc.collect()
    gc.freeze()
    t0 = common.now()
    w_open = t0 + tr["ramp_s"]
    w_close = w_open + seconds

    def submit(prompt, new, client, now):
        nonlocal failed, attempted
        attempted += 1
        req = loadgen.Request(0.0, prompt, new, client, 0)
        try:
            s = batcher.submit_ids(prompt, max_new_tokens=new)
        except Exception as e:  # noqa: BLE001 — a refusal is a failed request
            failed += 1
            print(f"benchmark: refused: {type(e).__name__}: {e}", file=sys.stderr)
            return
        live.append(Live(req, s, now, client))

    def poll(now):
        nonlocal failed
        new_tokens, done = 0, []
        for lv in live:
            n = len(lv.stream.emitted)
            if n > lv.seen:
                if lv.first_at is None:
                    lv.first_at, lv.slot = now, getattr(lv.stream, "slot", None)
                new_tokens += n - lv.seen
                lv.seen, lv.last_at = n, now
            if lv.stream.done() and len(lv.stream.emitted) == lv.seen:
                done.append(lv)
        for lv in done:
            live.remove(lv)
            if lv.seen == 0 or not ended_well(lv.stream):
                failed += 1
            else:
                finished.append(lv)
        return new_tokens, done

    # first wave: staggered lengths, so that streams do not end together
    for c in range(clients):
        prompt, new = next(source)
        submit(prompt, max(1, round(new * (c + 1) / clients)), c, t0)
    tracing = None
    setup_s = None
    while True:
        now = common.now()
        new_tokens, done = poll(now)
        for lv in done:
            prompt, new = next(source)
            submit(prompt, new, lv.client, now)
        if tracing is None and ctx.trace and now >= w_open - tr["trace_lead_s"]:
            # stalls this thread: before the window
            tracing = ctx.start_trace(python_tracer=False)
            continue
        if state["open"] is None and now >= w_open and new_tokens:
            state["open"], state["c0"] = now, counters.read()
            state["burst_at"] = now
            state["last_steps"] = state["c0"]["decode_steps"]
            setup_s = common.process_age_s()
            w_close = now + seconds
        elif state["open"] is not None:
            if new_tokens:
                state["tokens"] += new_tokens
                steps_now = batcher.metrics.decode_steps_total.value
                d = steps_now - state["last_steps"]
                if d > 0:
                    gaps.extend([(now - state["burst_at"]) / d] * d)
                    state["burst_at"] = now
                    state["last_steps"] = steps_now
                    state["bursts"] += d
                    state["kv_sum"] += d * sum(l.prompt_len + l.seen
                                               for l in live if l.seen)
                    state["pages_sum"] += d * engine.allocator.used_pages
            if now >= w_close and new_tokens:
                state["close"], state["c1"] = now, counters.read()
                break
        time.sleep(POLL_S)
    window = state["close"] - state["open"]
    trace = ctx.stop_trace(tracing)
    gc.unfreeze()
    peak = common.memory_peak_bytes(ctx.devices)
    batcher.stop(drain=False)
    retraced = state["c1"]["retraces"] - state["c0"]["retraces"]
    sample = pick_sample(finished, ctx.seed, tr["check_requests"])
    served = [(list(lv.req.prompt), list(lv.stream.emitted)) for lv in sample]
    n_finished = len(finished)
    load = engine.expert_load
    del engine, batcher, counters, live, finished, sample
    gc.collect()
    limits = dict(cell.config["check"])
    if ctx.rehearse:
        limits.update(cell.rehearsal("check"))
    checks = compare(served, ctx.seed, sizes, banned, limits)
    if os.environ.get("BENCHMARK_CONTROL"):
        # a builder's look (PERF.md section 2): the reference computed one
        # precision lower, in the program's place, under the same limits
        control(served, ctx.seed, sizes, banned, limits,
                os.environ["BENCHMARK_CONTROL"])
    checks.add("compiled_in_window", float(retraced), 0.0,
               "programs traced after the window opened")
    checks.emit()
    c0, c1 = state["c0"], state["c1"]
    delta = {k: c1[k] - c0[k] for k in c0}
    obs = {
        "counters": {
            **delta, "window_s": window, "slots": eng_slots, "n_pages": n_pages,
            "tokens_seen": state["tokens"],
            "live_rows_sum": delta["occupancy_sum"] * eng_slots,
            "slot_steps": delta["occupancy_n"] * eng_slots,
            "live_kv_tokens_sum": state["kv_sum"],
            "pages_live_sum": state["pages_sum"], "bursts": state["bursts"],
            "pages_steps": state["bursts"] * n_pages,
            "prefix_lookups": delta["prefix_full"] + delta["prefix_partial"]
            + delta["prefix_miss"],
            "memory_peak_bytes": peak, "finished": n_finished,
        },
        "samples": {}, "trace": trace, "sizes": sizes, "peaks": ctx.peaks,
    }
    if ctx.trace:
        obs["counters"].update(layer_numbers(obs, spans.records(obs), load))
    common.say({"window_s": window, "finished": n_finished,
                "tokens_seen": state["tokens"], "round_ms": round_ms(gaps),
                "counters": {
                    k: v for k, v in obs["counters"].items()
                    if isinstance(v, (int, float))}})
    e2e = {"setup_s": setup_s, "decode_tokens_per_s": state["tokens"] / window}
    return {"checks": checks.rows, "correct": checks.correct,
            "attempted": attempted, "failed": failed, "end_to_end": e2e,
            "obs": obs, "memory_peak_bytes": peak}


def round_ms(gaps: list) -> dict:
    """The worker's rounds as the poll saw them (1 ms apart), on every run:
    a round that held a prefill is at least 1.5 times the median one.  A
    whole run that is slow shows here as slower decode rounds, slower
    prefills, or both (PERF.md section 6)."""
    if not gaps:
        return {}
    ms = sorted(1e3 * g for g in gaps)
    mid = ms[len(ms) // 2]
    plain = [g for g in ms if g < 1.5 * mid]
    held = [g for g in ms if g >= 1.5 * mid]
    out = {"rounds": len(ms), "decode_p50": plain[len(plain) // 2],
           "decode_p90": plain[len(plain) * 9 // 10],
           "decode_mean": sum(plain) / len(plain)}
    if held:
        out.update(with_prefill=len(held), with_prefill_mean=sum(held) / len(held),
                   with_prefill_p50=held[len(held) // 2])
    return out


def layer_numbers(obs: dict, recs: list, load) -> dict:
    """What no fixed reducer computes, for ``counter`` / ``ratio`` metric
    files: keys left out where there is nothing to read (a program without
    these leaves or attributes)."""
    out, c = {}, obs["counters"]
    # the readers of these four for the BERT cells are ``.py`` files, which
    # ``tests/test_spans.py`` holds at ten with one cell each
    for key, v in (("host_exposed_ms_a_step", spans.host_exposed_ms_per_step(recs)),
                   ("emit_ms_a_step", spans.per_step_ms(recs, "emit")),
                   ("fetch_ms_a_step", spans.per_step_ms(recs, "fetch")),
                   ("admit_ms_a_seat", spans.admit_ms_per_seat(recs))):
        if v is not None:
            out[key] = v

    def total(leaf, attr):
        vals = [(r.get("attrs") or {}).get(attr) for r in recs
                if r.get("name") == leaf]
        vals = [v for v in vals if v is not None]
        return float(sum(vals)) if vals else None

    for key, leaf, attr in (
            ("kv_positions_read", "decode.dispatch", "kv_positions_read"),
            ("kv_positions_live", "decode.dispatch", "kv_positions_live"),
            ("expert_assignments_decode", "decode.fetch", "expert_assignments"),
            ("expert_assignments_prefill", "prefill.fetch", "expert_assignments")):
        v = total(leaf, attr)
        if v is not None:
            out[key] = v
    out["decode_leaves"] = float(spans.steps(recs))
    if load is not None and load.sum() > 0:
        out["expert_load_max_over_mean"] = float(load.max() / load.mean())
    t, peaks, sizes = obs.get("trace"), obs["peaks"], obs["sizes"]
    if not t or not peaks:
        return out
    progs = t["programs"]

    def device(name):
        hits = [v for k, v in progs.items() if name in k]
        return (sum(h["seconds"] for h in hits),
                sum(h["launches"] for h in hits))

    dec_s, dec_n = device("_pdecode_fn")
    if dec_n and c.get("decode_steps"):
        per_layer = out.get("expert_assignments_decode")
        moe_layers = counts_axk1.layers(sizes)[1]
        least = counts_axk1.decode_step_min_seconds(
            sizes, rows=c["live_rows_sum"] / c["decode_steps"],
            live_tokens=c["live_kv_tokens_sum"] / max(c["bursts"], 1),
            peak=peaks, assignments=None if not per_layer else
            per_layer / out["decode_leaves"] / moe_layers)
        out["decode_least_s"] = least["seconds"] * dec_n
        out["decode_device_s"] = dec_s
        obs.setdefault("notes", {})["decode_bound"] = least["bound"]
    pre_s, pre_n = device("_prefill_fn")
    if pre_n and c.get("prefills"):
        # the window's mean prompt: least(mean) <= mean(least), the
        # attention being quadratic, so this share reads low, never high
        least = counts_axk1.prefill_min_seconds(
            sizes, tokens=c["prefill_tokens"] / c["prefills"], peak=peaks)
        out["prefill_least_s"] = least["seconds"] * pre_n
        out["prefill_device_s"] = pre_s
    return out


def compare(served, seed, sizes, banned, limits, prec="f32") -> common.Checks:
    checks = common.Checks()
    gaps, margins = reference_gaps(served, seed, sizes, banned, prec)
    judge(checks, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    return checks


def control(served, seed, sizes, banned, limits, lowprec) -> None:
    """Prints the verdict on the reference computed in ``lowprec`` put in
    the program's place; judges nothing."""
    gaps, margins = reference_gaps(served, seed, sizes, banned,
                                   lowprec=lowprec)
    rows = common.Checks()
    judge(rows, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    common.say({"control": lowprec, "correct": rows.correct,
                "checks": {r["check"]: [r["value"], r["ok"]]
                           for r in rows.rows}})


def judge(checks, requests, limits) -> None:
    """``requests``: per sampled request, (gap, routing margin) of every
    served position."""
    lim, margin = limits["served_logit_gap"], limits["swap_margin"]
    flat = [gm for r in requests for gm in r]
    n_requests = len(requests)
    clear = [g for g, m in flat if m >= margin]
    tied = [g for g, m in flat if m < margin]
    swaps = sum(1 for g in tied if g > limits["swap_gap"])
    n = len(flat)
    common.say({"routing": {
        "served": n, "near_tie": len(tied), "swaps": swaps,
        "by_margin": {str(x): {
            "near_tie_share": sum(1 for _, m in flat if m < x) / max(n, 1),
            "widest_clear_gap": max((g for g, m in flat if m >= x), default=0.0)}
            for x in (0.0, 0.003, 0.01, 0.015, 0.02, 0.03)},
        "widest_near_tie_gap": max(tied, default=0.0),
        # no limit yet (PERF.md section 7): the share of ONE request's
        # positions that are swaps, which a fault of one slot would raise
        "worst_request_swap_share": max(
            (sum(1 for g, m in r if m < margin and g > limits["swap_gap"])
             / max(len(r), 1) for r in requests), default=0.0)}})
    checks.add("served_logit_gap", max(clear, default=float("nan")), lim,
               f"{len(clear)} served tokens of {n_requests} requests whose "
               f"routing margin is at least {margin}, against the float32 "
               "reference")
    checks.add("routing_swap_share", swaps / max(n, 1),
               limits["routing_swap_share"],
               f"{swaps} of {len(tied)} near-tie positions lie further off "
               f"than {limits['swap_gap']}: swaps allowed only below the "
               "margin, counted")
    checks.add("served_tokens_compared", float(len(clear)),
               float(limits["min_served_tokens"]),
               "served tokens outside near ties", at_least=True)


def reference_gaps(served, seed, sizes, banned, prec="f32", lowprec=None):
    """Per request, per served token: (reference's best logit minus the
    served token's logit, the position's routing margin).  With ``lowprec``
    the token judged at each position is the one the reference computed in
    that precision puts first (the control)."""
    import numpy as np

    from benchmark.reference import axk1

    longest = max(len(p) + len(e) for p, e in served)
    width = -(-longest // REF_PAD) * REF_PAD
    seqs, at = [], []
    for prompt, emitted in served:
        seq = list(prompt) + list(emitted)
        seqs.append(seq + [0] * (width - len(seq)))     # causal: padding
        at.append(list(range(len(prompt) - 1, len(seq) - 1)))   # after, unseen
    out = axk1.forward(seed, sizes, seqs, banned=banned, prec=prec, at=at)
    low = (axk1.forward(seed, sizes, seqs, banned=banned, prec=lowprec, at=at)
           if lowprec is not None else None)
    gaps, margins = [], []
    for i, (prompt, emitted) in enumerate(served):
        logits, margin = np.asarray(out[i][0]), np.asarray(out[i][1])
        nxt = (np.asarray(emitted) if low is None
               else np.argmax(np.asarray(low[i][0]), axis=-1))
        got = np.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        gaps.append([float(x) for x in logits.max(-1) - got])
        margins.append([float(x) for x in margin[at[i]]])
    return gaps, margins
