"""Traffic kind ``closed_loop_latent_mhc``: the closed loop of the other two
share families — as many clients as the traffic says, each sending its next
distinct prompt when the last one finished, a cycle's prompts in a levelled
order — driving the latent-attention decoder whose residual is FOUR STREAMS
mixed by manifold-constrained hyper-connections, its experts held whole
behind a bias-corrected router, through the SAME ``PagedDecodeEngine`` /
``DecodeBatcher`` and the same latent family of ``models/families.py`` as
``closed_loop_latent_moe``'s configuration.

**The loop is imported, not copied.**  ``run`` is
``closed_loop_hybrid_linear.run``'s code — the latent kind's loop with the
window laid on WHOLE PERIODS of it (``PeriodWindow``: both edges on a burst
that carries a stream's first token, the end of a prefill launch) and a
group of ``strata`` prompts dealt folded (``folded_prompts``: the levelled
order, the parts' order fixed) — over THIS module's names (``_with``): where
that code says ``build``, ``compare``, ``control`` or ``layer_numbers`` it
finds the ones below, and ``model_sizes`` is the latent kind's.  Why that
window here: a launch of this cell is 0.05-0.13 s and a stream ends every 4
decode steps, so a window whose edges fall on any burst holds one stretch
of plain steps more or less by the phase at which the ramp ends (the latent
cell's set of six spreads 5.7 % under such edges, the hybrid's 2.9 % under
these: ledger, PR 36).

What is this configuration's is stated here:

- ``build``: the latent kind's, but for the vocabulary file — the
  generator's alphabet ends at 27 489 tokens and this configuration's ids
  come from all 131 072 rows, so the file goes on with tokens of the form
  ``w<id>`` (ids are what the traffic sends; no text is ever tokenized);
  prefix sharing stays ON (only latents are cached);
- ``make_weights``: from ``reference/xing4.py``, a layer at a time, laid out
  as the program's trees (the mixing's leaves and the selection bias among
  them) BEFORE the engine and its page pool exist;
- ``compare`` / ``control``: the reference runs the sampled requests padded
  to ONE length, ``GROUP`` of them at a time (a sequence's four float32
  streams are 205 MB at 3 584 positions: 32 at once would be 6.6 GB beside
  a layer's float32 weights), a layer's weights at a time; the control
  lowers either every matmul's operands (``bf16``, ``fp8``) or the precision
  of the MIXING alone (``mix-bf16``);
- ``layer_numbers``: the latent kind's host numbers, leaves' sums and expert
  load, with this configuration's rooflines (``counts_xing4``: the mixing's
  bytes and operations among the least a step needs) and the mixing's share
  of the least bytes a decode step must move.

``correct`` is the latent kind's ``judge``: the widest gap by which a served
token's logit lies below the reference's best, outside near-tie routing;
swaps counted and their share limited; enough served tokens compared.
"""
from __future__ import annotations

import functools
import os
import types

from benchmark import common, counts_xing4, loadgen
from benchmark.kinds import closed_loop_hybrid_linear as periods
from benchmark.kinds import closed_loop_latent_moe as latent
from benchmark.kinds.closed_loop_latent_moe import REF_PAD, judge, model_sizes

GROUP = 8          # sampled requests the reference holds at once


def _with(fn, **names):
    """``fn``'s code over its own module's names with ``names`` in their
    place: the same loop, calling this kind's parts."""
    return types.FunctionType(fn.__code__, {**fn.__globals__, **names},
                              fn.__name__, fn.__defaults__, fn.__closure__)


def make_weights(like, seed: int, sizes: dict, banned: tuple):
    """The benchmark's seeded weights as the program's trees ``like``
    (shapes): every stacked leaf is filled a layer at a time through a
    donated update, so the peak is the model plus one layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import xing4

    key = xing4.seed_key(seed)
    top = jax.jit(lambda k: xing4.top_weights(k, sizes, banned))(key)
    dense_n = int(sizes["first_k_dense_replace"])

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
    def fill(stack, k, l, at):
        new = xing4.layer_weights(k, sizes, l)
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
            f"reference/xing4.py lays out\n  benchmark: {got}\n"
            f"  program:   {like}")
    return params, head


def vocab_lines(vocab_size: int) -> list:
    """``loadgen.vocab_lines`` carried on to ``vocab_size`` distinct tokens:
    its alphabet (one ideograph a token) ends at 27 489."""
    lines = loadgen.vocab_lines(vocab_size)
    return lines + [f"w{i}" for i in range(len(lines), vocab_size)]


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
    # a program without this preset ends here, before anything is built
    cfg = get_config(prog["model"], vocab_size=int(sizes["vocab_size"]))
    vocab = os.path.join(wd, "vocab.txt")
    with open(vocab, "w", encoding="utf-8") as f:
        f.write("\n".join(vocab_lines(cfg.vocab_size)) + "\n")
    args = Args(vocab_path=vocab, output_dir=wd, data_path=vocab,
                max_seq_len=eng["max_len"], decode_max_len=eng["max_len"],
                decode_slots=eng["slots"], kv_page_sz=eng["page_size"],
                kv_hbm_mb=eng.get("kv_hbm_mb", 0.0), kv_layout="paged",
                seed=ctx.seed % (2 ** 31 - 1), **prog)
    tok = WordPieceTokenizer(load_vocab(vocab))
    if tok.vocab_size != cfg.vocab_size:
        raise SystemExit(f"benchmark: the vocabulary file holds "
                         f"{tok.vocab_size} tokens, not {cfg.vocab_size}")
    banned = (tok.sep_id,)
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
        if k in ("cache_bytes", "kv_pool_bytes", "weights_bytes",
                 "stream_bytes_a_token")}})
    return engine, batcher, banned


def layer_numbers(obs: dict, recs: list, load) -> dict:
    """What no fixed reducer computes, for ``counter`` / ``ratio`` metric
    files: keys left out where there is nothing to read (a program without
    these leaves or attributes)."""
    # the one-stream rooflines have no meaning here: the host numbers, the
    # leaves' sums and the experts' load are what is taken from that kind
    out = latent.layer_numbers({**obs, "trace": None}, recs, load)
    c, sizes = obs["counters"], obs["sizes"]
    t, peaks = obs.get("trace"), obs["peaks"]
    if not peaks or not c.get("decode_steps"):
        return out
    per_layer = out.get("expert_assignments_decode")
    least = counts_xing4.decode_step_min_seconds(
        sizes, rows=c["live_rows_sum"] / c["decode_steps"],
        live_tokens=c["live_kv_tokens_sum"] / max(c["bursts"], 1),
        peak=peaks, assignments=None if not per_layer else
        per_layer / out["decode_leaves"] / counts_xing4.layers(sizes)[1])
    if out.get("decode_leaves"):
        # only a program that records the decode leaves runs this family
        out["mhc_bytes_a_step"] = least["mixing_bytes"]
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
        least = counts_xing4.prefill_min_seconds(
            sizes, tokens=c["prefill_tokens"] / c["prefills"], peak=peaks)
        out["prefill_least_s"] = least["seconds"] * pre_n
        out["prefill_device_s"] = pre_s
    return out


def _precisions(lowprec):
    """A control's name -> (matmul operands, the mixing)."""
    return ("f32", "bf16") if lowprec == "mix-bf16" else (lowprec, "f32")


def compare(served, seed, sizes, banned, limits, prec="f32") -> common.Checks:
    checks = common.Checks()
    t = common.now()
    gaps, margins = reference_gaps(served, seed, sizes, banned, prec)
    common.say({"reference_s": common.now() - t, "requests": len(served)})
    judge(checks, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    return checks


def control(served, seed, sizes, banned, limits, lowprec) -> None:
    """Prints the verdict on the reference computed in ``lowprec`` (``bf16``
    / ``fp8`` matmul operands, or ``mix-bf16``: every intermediate of the
    mixing rounded to bfloat16) put in the program's place; judges
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

    from benchmark.reference import xing4

    longest = max(len(p) + len(e) for p, e in served)
    width = -(-longest // REF_PAD) * REF_PAD
    seqs, at = [], []
    for prompt, emitted in served:
        seq = list(prompt) + list(emitted)
        seqs.append(seq + [0] * (width - len(seq)))     # causal: padding
        at.append(list(range(len(prompt) - 1, len(seq) - 1)))   # after, unseen

    def forward(**kw):
        out = []
        for g in range(0, len(seqs), GROUP):
            got = xing4.forward(seed, sizes, seqs[g:g + GROUP], banned=banned,
                                at=at[g:g + GROUP], **kw)
            out += [(np.asarray(lg), np.asarray(m)) for lg, m in got]
        return out

    out = forward(prec=prec)
    low = None
    if lowprec is not None:
        mm, mix = _precisions(lowprec)
        low = forward(prec=mm, mix=mix)
    gaps, margins = [], []
    for i, (prompt, emitted) in enumerate(served):
        logits, margin = out[i]
        nxt = (np.asarray(emitted) if low is None
               else np.argmax(low[i][0], axis=-1))
        got = np.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        gaps.append([float(x) for x in logits.max(-1) - got])
        margins.append([float(x) for x in margin[at[i]]])
    return gaps, margins


run = _with(periods.run, model_sizes=model_sizes, build=build, compare=compare,
            control=control, layer_numbers=layer_numbers)
