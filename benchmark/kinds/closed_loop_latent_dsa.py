"""Traffic kind ``closed_loop_latent_dsa``: the closed loop of the other share
families — as many clients as the traffic says, each sending its next
distinct prompt when the last one finished, a cycle's prompts in a levelled
order — driving the latent-attention decoder under a LEARNED SPARSE
attention (a lightning indexer in the ``full`` layers, its picks reused by
the ``shared`` layers after them, a second, narrower pool of index keys
through the latents' page table) through the SAME ``PagedDecodeEngine`` /
``DecodeBatcher`` and the same latent family of ``models/families.py`` as
``closed_loop_latent_moe``'s and ``closed_loop_latent_mhc``'s configurations.

**The loop is imported, not copied**, as ``closed_loop_latent_mhc`` imports
it: ``run`` is ``closed_loop_hybrid_linear.run``'s code — the window laid on
WHOLE PERIODS of the loop (``PeriodWindow``), a group of ``strata`` prompts
dealt folded (``folded_prompts``) — over THIS module's names (``_with``).
Why that window here: a prefill launch of this cell is some 0.5 s and a
stream ends every 16 decode steps, so a window whose edges fall on any burst
holds one launch more or less by the phase at which the ramp ends.

What is this configuration's is stated here:

- ``model_sizes``: the configuration's numbers plus its ``rope_parameters``
  group and its ``indexer_types`` (the layers held);
- ``make_weights``: from ``reference/glm52.py``, a layer at a time, in the
  program's layout (``program_layout``: the rotary columns de-interleaved),
  the indexers a stack of their own, BEFORE the engine and its pools exist;
- ``build``: the four-stream kind's over this module's ``make_weights``; ids
  come from the 19 360-row slice; prefix sharing stays ON (a page id names
  the same positions in both pools);
- ``compare`` / ``control``: the reference runs the sampled requests padded
  to ONE length, ``GROUP`` of them at a time, a layer's weights at a time;
  a control lowers every matmul's operands (``bf16``, ``fp8``) or replaces
  every query's picks by the most recent ``index_topk`` positions
  (``sel-recent``: the mechanism left out); several, comma-separated, in
  ``BENCHMARK_CONTROL`` are run one after another;
- ``layer_numbers``: the latent kind's host numbers, leaves' sums and expert
  load, with this configuration's rooflines (``counts_glm52``: a held expert
  read only if a row chose it, the index keys of each row's context in the
  ``full`` layers, the PICKED latents in every layer), the index keys' share
  of the least bytes a decode step must move, and what the selection
  counted: positions visible and picked (the ``decode.fetch`` leaves) and
  latent positions read (``decode.dispatch``).

``correct`` is the latent kind's ``judge``: the widest gap by which a served
token's logit lies below the reference's best, outside near-tie routing;
swaps counted and their share limited; enough served tokens compared.
"""
from __future__ import annotations

import functools

from benchmark import common, counts_glm52
from benchmark.kinds import closed_loop_hybrid_linear as periods
from benchmark.kinds import closed_loop_latent_mhc as mhc
from benchmark.kinds import closed_loop_latent_moe as latent
from benchmark.kinds.closed_loop_latent_mhc import _with
from benchmark.kinds.closed_loop_latent_moe import REF_PAD, judge

GROUP = 8          # sampled requests the reference holds at once


def model_sizes(cell, rehearse: bool) -> dict:
    """The configuration's numbers plus its ``rope_parameters`` group and
    the ``indexer_types`` of the layers that are run."""
    sizes = cell.sizes(rehearse)
    sizes["rope_parameters"] = dict(cell.config["rope_parameters"])
    kinds = (rehearse and cell.rehearsal("indexer_types")) \
        or cell.config["indexer_types"]
    sizes["indexer_types"] = list(kinds)[:int(sizes["num_hidden_layers"])]
    return sizes


def make_weights(like, seed: int, sizes: dict, banned: tuple):
    """The benchmark's seeded weights as the program's trees ``like``
    (shapes): every stacked leaf is filled a layer at a time through a
    donated update, so the peak is the model plus one layer.  A ``full``
    layer's indexer goes to the indexers' own stack, by its order."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import glm52

    key = glm52.seed_key(seed)
    top = jax.jit(lambda k: glm52.top_weights(k, sizes, banned))(key)
    dense_n = int(sizes["first_k_dense_replace"])
    full = [l for l in range(int(sizes["num_hidden_layers"]))
            if glm52.is_full(sizes, l)]

    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(3, 4))
    def fill(stack, indexers, k, l, at):
        new = glm52.program_layout(glm52.layer_weights(k, sizes, l), sizes)
        if "indexer" in new:
            indexers = jax.tree_util.tree_map(
                lambda s, x: s.at[full.index(l)].set(x), indexers,
                new.pop("indexer"))
        return jax.tree_util.tree_map(lambda s, x: s.at[at].set(x), stack,
                                      new), indexers

    def zeros(shapes):
        return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                      shapes)

    indexers = zeros(like[0]["indexer"])
    stacks = {}
    for part, first in (("dense", 0), ("moe", dense_n)):
        stack = zeros(like[0][part])
        for i in range(jax.tree_util.tree_leaves(stack)[0].shape[0]):
            stack, indexers = fill(stack, indexers, key, first + i, i)
        stacks[part] = stack
    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "indexer": indexers, **stacks}
    head = {"kernel": top["head"]}
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, head))
    if got != like:
        raise SystemExit(
            "benchmark: the program's parameter tree is not the one "
            f"reference/glm52.py lays out\n  benchmark: {got}\n"
            f"  program:   {like}")
    return params, head


# the four-stream kind's ``build`` (the preset looked up before anything is
# built, a vocabulary file of the slice's 19 360 rows, the weights made before
# the engine and its pools exist, prefix sharing left on) over THIS module's
# ``make_weights``
build = _with(mhc.build, make_weights=make_weights)


def _leaf_sum(recs: list, leaf: str, attr: str):
    vals = [(r.get("attrs") or {}).get(attr) for r in recs
            if r.get("name") == leaf]
    vals = [v for v in vals if v is not None]
    return float(sum(vals)) if vals else None


def layer_numbers(obs: dict, recs: list, load) -> dict:
    """What no fixed reducer computes, for ``counter`` / ``ratio`` metric
    files: keys left out where there is nothing to read (a program without
    these leaves or attributes)."""
    # the dense-attention rooflines have no meaning here: the host numbers,
    # the leaves' sums and the experts' load are what is taken from that kind
    out = latent.layer_numbers({**obs, "trace": None}, recs, load)
    for key, leaf, attr in (
            ("positions_visible_decode", "decode.fetch", "positions_visible"),
            ("positions_picked_decode", "decode.fetch", "positions_picked"),
            ("positions_visible_prefill", "prefill.fetch", "positions_visible"),
            ("positions_picked_prefill", "prefill.fetch", "positions_picked")):
        v = _leaf_sum(recs, leaf, attr)
        if v is not None:
            out[key] = v
    c, sizes = obs["counters"], obs["sizes"]
    t, peaks = obs.get("trace"), obs["peaks"]
    if not peaks or not c.get("decode_steps"):
        return out
    leaves = out.get("decode_leaves")
    per_layer = out.get("expert_assignments_decode")
    picked = out.get("positions_picked_decode")
    least = counts_glm52.decode_step_min_seconds(
        sizes, rows=c["live_rows_sum"] / c["decode_steps"],
        live_tokens=c["live_kv_tokens_sum"] / max(c["bursts"], 1),
        peak=peaks, assignments=None if not per_layer else
        per_layer / leaves / counts_glm52.layers(sizes)[1],
        picked=None if not picked else picked / leaves)
    if picked:
        # only a program that counts its picks runs this configuration
        out["index_bytes_a_step"] = least["index_bytes"]
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
        # the window's mean prompt: the least time is convex in a prompt's
        # length (index scores quadratic, picked pairs linear past
        # index_topk), so least(mean) <= mean(least): this share reads low,
        # never high
        least = counts_glm52.prefill_min_seconds(
            sizes, tokens=c["prefill_tokens"] / c["prefills"], peak=peaks)
        out["prefill_least_s"] = least["seconds"] * pre_n
        out["prefill_device_s"] = pre_s
    return out


def _precisions(lowprec):
    """A control's name -> (matmul operands, the selection)."""
    return ("f32", "recent") if lowprec == "sel-recent" else (lowprec, "index")


def compare(served, seed, sizes, banned, limits, prec="f32") -> common.Checks:
    checks = common.Checks()
    t = common.now()
    gaps, margins = reference_gaps(served, seed, sizes, banned, prec)
    common.say({"reference_s": common.now() - t, "requests": len(served)})
    judge(checks, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)], limits)
    return checks


def control(served, seed, sizes, banned, limits, lowprec) -> None:
    """Prints the verdict on the reference computed as ``lowprec`` says
    (``bf16`` / ``fp8`` matmul operands, or ``sel-recent``: every query's
    picks replaced by the most recent ``index_topk`` positions; several,
    comma-separated, one after another) put in the program's place; judges
    nothing."""
    for name in lowprec.split(","):
        gaps, margins = reference_gaps(served, seed, sizes, banned,
                                       lowprec=name)
        rows = common.Checks()
        judge(rows, [list(zip(gs, ms)) for gs, ms in zip(gaps, margins)],
              limits)
        common.say({"control": name, "correct": rows.correct,
                    "checks": {r["check"]: [r["value"], r["ok"]]
                               for r in rows.rows}})


def reference_gaps(served, seed, sizes, banned, prec="f32", lowprec=None):
    """Per request, per served token: (reference's best logit minus the
    served token's logit, the position's routing margin).  With ``lowprec``
    the token judged at each position is the one the reference computed
    that way puts first (the control)."""
    import numpy as np

    from benchmark.reference import glm52

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
            got = glm52.forward(seed, sizes, seqs[g:g + GROUP], banned=banned,
                                at=at[g:g + GROUP], **kw)
            out += [(np.asarray(lg), np.asarray(m)) for lg, m in got]
        return out

    out = forward(prec=prec)
    low = None
    if lowprec is not None:
        mm, select = _precisions(lowprec)
        low = forward(prec=mm, select=select)
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
