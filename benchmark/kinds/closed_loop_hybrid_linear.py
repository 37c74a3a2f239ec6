"""Traffic kind ``closed_loop_hybrid_linear``: the closed loop of
``closed_loop_latent_moe`` (as many clients as the traffic says, each sending
its next distinct prompt when the last one finished, a cycle's prompts in a
levelled order) driving the hybrid decoder — gated delta-rule linear
attention with a PER-SLOT recurrent state beside paged GQA layers, sparse
experts in every layer — through the SAME ``PagedDecodeEngine`` /
``DecodeBatcher`` as the other two families.

What ``kinds/closed_loop_latent_moe.py`` has is used from there: its
``round_ms`` and ``judge``, and through ``kinds/serve.py`` the rest.  What is
the latent family's there is stated again here:

- ``build``: the weights come from ``reference/solar_open2.py``, a layer at
  a time, as the program's tree (every layer's leaves its own), BEFORE the
  engine, its page pool and its slots' states exist; the engine is built
  with ``prefix_share=False`` (the family refuses sharing: no snapshot of a
  recurrent state exists at a page boundary);
- ``compare`` / ``control``: the reference runs the sampled requests padded
  to ONE length, the linear layers by their sequential recurrence; the
  control lowers either the matmuls' operands (``bf16``, ``fp8``) or the
  precision the recurrent state is held in between positions
  (``state-bf16``);
- ``layer_numbers``: the latent kind's (host numbers of the leaves, cache
  positions read and live, assignments to held experts) with this family's
  rooflines (``counts_solar_open2``) and the bytes of recurrent state a
  decode step moves (``state_bytes`` of ``decode.dispatch``) beside the
  bytes the step must move at all.

The loop (``run``) is the latent kind's but for two things, both because a
prefill here is 0.23-0.42 s — 1.1 % of the window each — and a stream ends
every 8 decode steps, so half of the window is prefill launches:

- **the window's edges lie on bursts that carry a stream's FIRST token**
  (``PeriodWindow``), the end of a prefill launch.  The window then holds
  WHOLE periods of the loop — a launch and the decode steps up to the next —
  whatever the phase at which the ramp ends.  With the edges on any burst a
  window held 46 launches beside 367-369 decode steps or beside 373-377,
  by that phase alone, and every run read 781-787 or 795-803 tokens/s, the
  same seed now one and now the other (PERF.md section 6, PR 35);
- **a group of ``strata`` prompts is dealt in a FOLDED order**
  (``folded_prompts``): shortest part, longest, second shortest, second
  longest ... so the launches that a window's first and last partial group
  hold cost the mix's mean to within one prompt, where a seeded order could
  put the three longest there.

``decode_tokens_per_s`` is what it is in every closed-loop cell: the tokens
seen between two bursts over the seconds between them.

``correct`` is the latent kind's: the widest gap by which a served token's
logit lies below the reference's best, outside near-tie routing; swaps
counted and their share limited; enough served tokens compared.
"""
from __future__ import annotations

import gc
import os
import random
import sys
import time

from benchmark import common, counts_solar_open2, loadgen, spans
from benchmark.kinds import closed_loop_latent_moe as latent
from benchmark.kinds.closed_loop_latent_moe import REF_PAD, judge, round_ms
from benchmark.kinds.serve import (POLL_S, Counters, Live, ended_well,
                                   pick_sample)


def model_sizes(cell, rehearse: bool) -> dict:
    """The configuration's numbers plus its ``linear_attn_config`` group and
    the GQA layers of the depth that is run."""
    sizes = cell.sizes(rehearse)
    sizes["linear_attn_config"] = dict(cell.config["linear_attn_config"])
    if rehearse:
        sizes["linear_attn_config"].update(
            cell.rehearsal("linear_attn_config"))
    sizes["gqa_layers"] = [l for l in cell.config["gqa_layers"]
                           if l < sizes["num_hidden_layers"]]
    return sizes


def make_weights(like, seed: int, sizes: dict, banned: tuple):
    """The benchmark's seeded weights as the program's trees ``like``
    (shapes): a layer's leaves are its own, made one layer a launch, so the
    peak is the model plus one layer's float32 draw."""
    import jax

    from benchmark.reference import solar_open2 as ref

    key = ref.seed_key(seed)
    top = jax.jit(lambda k: ref.top_weights(k, sizes, banned))(key)
    make = jax.jit(lambda k, l: ref.layer_weights(k, sizes, l),
                   static_argnums=(1,))
    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "layers": [make(key, l)
                         for l in range(int(sizes["num_hidden_layers"]))]}
    head = {"kernel": top["head"]}
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, head))
    if got != like:
        raise SystemExit(
            "benchmark: the program's parameter tree is not the one "
            f"reference/solar_open2.py lays out\n  benchmark: {got}\n"
            f"  program:   {like}")
    return params, head


def folded_prompts(traffic: dict, seed: int, vocab_size: int):
    """``latent.levelled_prompts`` — every ``cycle`` requests hold the same
    multiset of lengths, cut into ``strata`` equal parts by length, every
    ``strata`` consecutive requests holding one length of each part — with a
    group DEALT in a folded order: the shortest part, the longest, the
    second shortest, the second longest ...  Which length of a part a group
    gets is the seed's; the order of the parts is not.

    Why: a prompt's launch costs by its bucket (about 0.23 / 0.31 / 0.42 s
    at 3 072 / 4 096 / 5 632) and a window holds 46 of a cycle's 64: five
    groups and most of a sixth, cut at both edges.  Seeded, the cut groups
    can hold the three longest or the three shortest, 0.3 s = 1 % of the
    window apart; folded, any run of consecutive prompts costs the mix's
    mean to within one prompt."""
    cycle = traffic["cycle"]
    strata = min(traffic["strata"], cycle)
    if cycle % strata:
        raise SystemExit(f"benchmark: a cycle of {cycle} requests cannot be "
                         f"cut into {strata} equal parts")
    rng = random.Random(seed)
    lengths = sorted(int(round(x)) for x in
                     loadgen.quantiles(traffic["prompt_tokens"], cycle))
    per = cycle // strata
    fold = [i // 2 if i % 2 == 0 else strata - 1 - i // 2
            for i in range(strata)]
    while True:
        parts = [lengths[i * per:(i + 1) * per] for i in range(strata)]
        for part in parts:
            rng.shuffle(part)
        for j in range(per):
            for i in fold:
                yield (loadgen.token_ids(rng, parts[i][j], vocab_size),
                       traffic["new_tokens"])


class PeriodWindow:
    """The measured window, its edges on bursts that carry a stream's FIRST
    token: it opens on the first such burst at or after ``due`` and closes
    on the first one ``seconds`` or more later, so it holds whole periods of
    the loop (a prefill launch and the decode steps up to the next).  The
    opening burst's tokens are outside it, the closing burst's inside."""

    def __init__(self, due: float, seconds: float):
        self.due, self.seconds = due, seconds
        self.open = self.close = None
        self.tokens = 0

    def see(self, now: float, new_tokens: int, firsts: int):
        """One poll's burst; returns ``"open"`` / ``"close"`` on an edge."""
        if self.open is None:
            if now >= self.due and firsts:
                self.open, self.due = now, now + self.seconds
                return "open"
            return None
        self.tokens += new_tokens
        if now >= self.due and firsts:
            self.close = now
            return "close"
        return None


def build(cell, ctx, sizes, wd):
    """The engine and its batcher, warmed: every program the traffic's
    buckets can reach is compiled before this returns.  The weights are made
    BEFORE the engine, its page pool and its slots' states exist, and the
    engine is given them."""
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
    # a program without this family ends here, before anything is built
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
        prefill_rows=eng.get("prefill_rows"), prefix_share=False)
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
    jax.block_until_ready((engine._pools, getattr(engine, "_states", ())))
    common.say({"weights_s": made_s, "warmup_s": common.now() - t, "kv": {
        k: v for k, v in engine.kv_snapshot().items()
        if k in ("cache_bytes", "kv_pool_bytes", "state_pool_bytes",
                 "weights_bytes")}})
    return engine, batcher, banned


def layer_numbers(obs: dict, recs: list, load) -> dict:
    """What no fixed reducer computes, for ``counter`` / ``ratio`` metric
    files: keys left out where there is nothing to read (a program without
    these leaves or attributes)."""
    # the latent family's rooflines have no meaning here: its host numbers,
    # the leaves' sums and the experts' load are what is taken from it
    out = latent.layer_numbers({**obs, "trace": None}, recs, load)
    c, sizes = obs["counters"], obs["sizes"]
    state = [(r.get("attrs") or {}).get("state_bytes") for r in recs
             if r.get("name") == "decode.dispatch"]
    state = [v for v in state if v is not None]
    t, peaks = obs.get("trace"), obs["peaks"]
    if not peaks or not c.get("decode_steps"):
        return out
    per_layer = out.get("expert_assignments_decode")
    n_layers = int(sizes["num_hidden_layers"])
    least = counts_solar_open2.decode_step_min_seconds(
        sizes, rows=c["live_rows_sum"] / c["decode_steps"],
        live_tokens=c["live_kv_tokens_sum"] / max(c["bursts"], 1),
        peak=peaks, assignments=None if not per_layer else
        per_layer / out["decode_leaves"] / n_layers)
    if state:
        # what the launches moved of state, over what a step must move at all
        out["state_bytes_a_step"] = float(sum(state)) / len(state)
        out["least_bytes_a_step"] = least["bytes"]
    if not t:
        return out
    progs = t["programs"]

    def device(name):
        hits = [v for k, v in progs.items() if name in k]
        return (sum(h["seconds"] for h in hits),
                sum(h["launches"] for h in hits))

    dec_s, dec_n = device("_pdecode_fn")
    if dec_n:
        out["decode_least_s"] = least["seconds"] * dec_n
        out["decode_device_s"] = dec_s
        obs.setdefault("notes", {})["decode_bound"] = least["bound"]
    pre_s, pre_n = device("_prefill_fn")
    if pre_n and c.get("prefills"):
        # the window's mean prompt: least(mean) <= mean(least), attention
        # being quadratic, so this share reads low, never high
        least = counts_solar_open2.prefill_min_seconds(
            sizes, tokens=c["prefill_tokens"] / c["prefills"], peak=peaks)
        out["prefill_least_s"] = least["seconds"] * pre_n
        out["prefill_device_s"] = pre_s
    return out


def _precisions(lowprec):
    """A control's name -> (matmul operands, the recurrent state)."""
    return ("f32", "bf16") if lowprec == "state-bf16" else (lowprec, "f32")


def compare(served, seed, sizes, banned, limits, prec="f32") -> common.Checks:
    checks = common.Checks()
    gaps, margins = reference_gaps(served, seed, sizes, banned, prec)
    judge(checks, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    return checks


def control(served, seed, sizes, banned, limits, lowprec) -> None:
    """Prints the verdict on the reference computed in ``lowprec`` (``bf16``
    / ``fp8`` matmul operands, or ``state-bf16``: the recurrent state held
    in bfloat16 between positions) put in the program's place; judges
    nothing."""
    gaps, margins = reference_gaps(served, seed, sizes, banned,
                                   lowprec=lowprec)
    rows = common.Checks()
    judge(rows, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    common.say({"control": lowprec, "correct": rows.correct,
                "checks": {r["check"]: [r["value"], r["ok"]]
                           for r in rows.rows}})


def reference_gaps(served, seed, sizes, banned, prec="f32", lowprec=None):
    """Per request, per served token: (reference's best logit minus the
    served token's logit, the position's routing margin).  With ``lowprec``
    the token judged at each position is the one the reference computed in
    that precision puts first (the control)."""
    import numpy as np

    from benchmark.reference import solar_open2 as ref

    longest = max(len(p) + len(e) for p, e in served)
    width = -(-longest // REF_PAD) * REF_PAD
    seqs, at = [], []
    for prompt, emitted in served:
        seq = list(prompt) + list(emitted)
        seqs.append(seq + [0] * (width - len(seq)))     # causal: padding
        at.append(list(range(len(prompt) - 1, len(seq) - 1)))   # after, unseen
    out = ref.forward(seed, sizes, seqs, banned=banned, prec=prec, at=at)
    low = None
    if lowprec is not None:
        mm, state = _precisions(lowprec)
        low = ref.forward(seed, sizes, seqs, banned=banned, prec=mm,
                          state=state, at=at)
    gaps, margins = [], []
    for i, (prompt, emitted) in enumerate(served):
        logits, margin = np.asarray(out[i][0]), np.asarray(out[i][1])
        nxt = (np.asarray(emitted) if low is None
               else np.argmax(np.asarray(low[i][0]), axis=-1))
        got = np.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        gaps.append([float(x) for x in logits.max(-1) - got])
        margins.append([float(x) for x in margin[at[i]]])
    return gaps, margins


def run(cell, ctx) -> dict:
    """``latent.run`` with the window's edges on first tokens and the
    prompts in the folded order (the module's docstring says why)."""
    sizes = model_sizes(cell, ctx.rehearse)
    tr = dict(cell.traffic)
    if ctx.rehearse:
        tr.update(cell.rehearsal("traffic"))
        cell.traffic = tr
    wd = common.work_dir(cell.name)
    engine, batcher, banned = build(cell, ctx, sizes, wd)
    eng_slots, n_pages = engine.slots, engine.n_pages
    seconds = min(ctx.seconds, tr["trace_seconds"]) if ctx.trace else ctx.seconds
    source = folded_prompts(tr, ctx.seed, sizes["vocab_size"])
    clients = tr["clients"]
    counters = Counters(engine, batcher)
    live, finished = [], []
    failed = attempted = 0
    state = {"c0": None, "c1": None, "kv_sum": 0.0, "pages_sum": 0.0,
             "bursts": 0, "last_steps": 0}
    gaps = []      # seconds a decode step, as the poll saw the counter move
    gc.collect()
    gc.freeze()
    t0 = common.now()
    window = PeriodWindow(t0 + tr["ramp_s"], seconds)

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
        new_tokens, firsts, done = 0, 0, []
        for lv in live:
            n = len(lv.stream.emitted)
            if n > lv.seen:
                if lv.first_at is None:
                    lv.first_at, lv.slot = now, getattr(lv.stream, "slot", None)
                    firsts += 1
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
        return new_tokens, firsts, done

    # first wave: staggered lengths, so that streams do not end together
    for c in range(clients):
        prompt, new = next(source)
        submit(prompt, max(1, round(new * (c + 1) / clients)), c, t0)
    tracing = None
    setup_s = None
    while True:
        now = common.now()
        new_tokens, firsts, done = poll(now)
        for lv in done:
            prompt, new = next(source)
            submit(prompt, new, lv.client, now)
        if (tracing is None and ctx.trace
                and now >= window.due - tr["trace_lead_s"]):
            # stalls this thread: before the window
            tracing = ctx.start_trace(python_tracer=False)
            continue
        was_open = window.open is not None
        edge = window.see(now, new_tokens, firsts)
        if edge == "open":
            state["c0"] = counters.read()
            state["burst_at"] = now
            state["last_steps"] = state["c0"]["decode_steps"]
            setup_s = common.process_age_s()
        elif was_open and new_tokens:
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
        if edge == "close":
            state["c1"] = counters.read()
            break
        time.sleep(POLL_S)
    window_s = window.close - window.open
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
            **delta, "window_s": window_s, "slots": eng_slots,
            "n_pages": n_pages, "tokens_seen": window.tokens,
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
    common.say({"window_s": window_s, "finished": n_finished,
                "tokens_seen": window.tokens,
                "round_ms": {**round_ms(gaps),
                             "longest": 1e3 * max(gaps, default=0.0)},
                "counters": {
                    k: v for k, v in obs["counters"].items()
                    if isinstance(v, (int, float))}})
    e2e = {"setup_s": setup_s, "decode_tokens_per_s": window.tokens / window_s}
    return {"checks": checks.rows, "correct": checks.correct,
            "attempted": attempted, "failed": failed, "end_to_end": e2e,
            "obs": obs, "memory_peak_bytes": peak}
