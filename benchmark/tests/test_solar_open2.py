"""The hybrid configuration (gated delta-rule linear attention beside paged
GQA, sparse experts) and the cell this PR adds, on the CPU: counts from
shapes, the cell found as data, a ``--rehearse`` walk, ``correct`` coming
out false for planted faults, and the controls' verdicts."""
import argparse
import subprocess
import sys

import numpy as np
import pytest

from benchmark import common, counts_solar_open2 as counts
from benchmark.run import run_cell

CONFIG = "solar-open2-ep16-share"
CELL = "solar-decode-longdoc-saturated"
LATENT_CELL = "axk1-decode-longdoc-saturated"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "hbm_bytes": 16e9}


def config():
    return common.load_json(common.HERE, "configs", CONFIG + ".json")


def sizes_of(cfg, rehearse=False):
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    sizes["linear_attn_config"] = dict(cfg["linear_attn_config"])
    if rehearse:
        sizes.update(cfg["rehearse"]["sizes"])
        sizes["linear_attn_config"].update(
            cfg["rehearse"]["linear_attn_config"])
    sizes["gqa_layers"] = [l for l in cfg["gqa_layers"]
                           if l < sizes["num_hidden_layers"]]
    return sizes


def rehearse(workload, seed=5, seconds=2.0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=0, rehearse=True)
    assert run_cell(ns) == common.REHEARSAL_EXIT
    return ns.result


# ------------------------------------------------------ counts from shapes

def test_parameters_cache_and_state_bytes_from_the_published_widths():
    s = sizes_of(config())
    p = counts.parts(s)
    # ISSUE 35: 109.05 M a GQA mixer, 137.73 M a linear one, 17.04 M of
    # router + shared expert, 15.73 M a routed expert, 3.90 G held
    assert abs(p["gqa"] / 1e6 - 109.05) < 0.02
    assert abs(p["linear"] / 1e6 - 137.73) < 0.02
    assert (p["router"] + p["shared"]) / 1e6 == pytest.approx(17.04, abs=0.01)
    assert p["expert"] == p["shared"] == 3 * 4096 * 1280
    assert p["router"] == 4096 * 320
    assert counts.layers(s) == (2, 6)
    assert abs(counts.params_held(s) / 1e9 - 3.90) < 0.005
    assert counts.cache_bytes_per_token(s) == 2 * 2 * 1024 * 2 == 8192
    assert counts.state_bytes_per_slot(s) \
        == 6 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert counts.expected_assignments(s, 64) == 32.0
    assert 15.5 < counts.experts_touched(s, 32.0) < 16.5
    assert counts.experts_touched(s, 0.0) == 0.0


def test_the_programs_own_count_agrees():
    """The benchmark counts parameters from the configuration file, the
    program from its parameter tree: one number."""
    from pdnlp_tpu.models import get_config, hybrid_linear

    cfg = get_config(config()["program"]["model"])
    assert hybrid_linear.param_count(cfg) == counts.params_held(
        sizes_of(config()))


def test_a_decode_step_is_bound_by_bytes_and_a_prompt_by_flops():
    s = sizes_of(config())
    live = 64 * 4100
    step = counts.decode_step_min_seconds(s, 64, live, PEAK)
    assert step["bound"] == "bytes"
    # the state read and written: 2 x 64 slots x 26.05 MB = 3.3 GB of ~13
    assert step["state_bytes"] == 2 * 64 * counts.state_bytes_per_slot(s)
    assert 0.24 < step["state_bytes"] / step["bytes"] < 0.30
    assert 0.0140 < step["seconds"] < 0.0165
    # one more cached position: 8 192 bytes, and 2 layers x 64 heads x 256 x 2
    more = counts.decode_step_min_seconds(s, 64, live + 1, PEAK)
    assert more["bytes"] - step["bytes"] == 8192
    assert more["flops"] - step["flops"] == 2 * 64 * 256 * 2
    # fewer assignments touch fewer experts: their weights are not read
    few = counts.decode_step_min_seconds(s, 64, live, PEAK, assignments=4.0)
    assert few["bytes"] < step["bytes"]
    pre = counts.prefill_min_seconds(s, 3840, PEAK)
    assert pre["bound"] == "flops" and 0.045 < pre["seconds"] < 0.060
    # the 3 841st token: 2 490 MFLOP of matrices (ISSUE 35) + 50 of the
    # chunkwise delta rule + 252 of softmax attention over 3 840 positions
    per_token = (counts.prefill_min_seconds(s, 3841, PEAK)["flops"]
                 - pre["flops"]) / 1e6
    assert per_token == pytest.approx(2487 + 50 + 252, abs=3)


# ------------------------------------------------------- the cell as data

def test_the_configuration_file_holds_the_published_numbers():
    cfg = config()
    published = {"hidden_size": 4096, "num_attention_heads": 64,
                 "head_dim": 128, "num_key_value_heads": 8,
                 "intermediate_size": 10240, "moe_intermediate_size": 1280,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "max_position_embeddings": 1048576,
                 "first_k_dense_replace": 0, "gqa_interval": 3,
                 "n_shared_experts": 1, "routed_scaling_factor": 1,
                 "num_experts_per_tok": 8, "partial_rotary_factor": 1,
                 "use_rope": False, "use_gqa_gate": True,
                 "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
                 "norm_topk_prob": True, "tie_word_embeddings": False,
                 "model_type": "solar_open2"}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == ["n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"]) == (8, 20, 24576, 320)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320, "vocab_size": 196608}
    for key in ("source", "deployment", "precision", "assumed", "check",
                "rehearse", "program"):
        assert cfg[key], key
    a = cfg["assumed"]
    assert a["slots"] * a["max_len"] == a["pool_pages"] * a["page_size"]
    for item in ("gqa_gate", "kda_low_rank", "kda_activation", "kda_decay",
                 "moe", "dtypes", "why"):
        assert a[item], item


def test_the_new_cell_is_found_and_reports_what_it_says():
    cell = common.Cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["kind"] == "closed_loop_hybrid_linear"
    assert [m["name"] for m in cell.end_to_end()] == ["decode_tokens_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in cell.per_layer()}
    latent = {m["name"] for m in common.Cell(LATENT_CELL).per_layer()}
    # the shared ``.json`` readers the latent cell reads, less the prefix
    # hit rate: this engine makes no lookup, so that reader finds nothing
    assert names & latent == {m for m in latent if m.startswith("sat_")} \
        - {"sat_prefix_hit_pct"}
    own = names - latent
    assert len(own) == 11 and all(n.startswith("solar_") for n in own)
    assert sum("roofline" in n for n in own) == 2
    tr = cell.traffic
    assert tr["clients"] == cell.config["assumed"]["slots"] == tr["cycle"] == 64
    assert (tr["prompt_tokens"]["hi"] + tr["new_tokens"]
            <= cell.config["assumed"]["max_len"])
    assert max(tr["buckets"]) >= tr["prompt_tokens"]["hi"]
    assert tr["cycle"] % tr["strata"] == 0


def test_the_loop_calls_this_kinds_parts_and_the_latent_kinds_judge():
    from benchmark.kinds import closed_loop_hybrid_linear as kind
    from benchmark.kinds import closed_loop_latent_moe as latent

    g = kind.run.__globals__
    assert g["build"] is kind.build and g["compare"] is kind.compare
    assert g["layer_numbers"] is kind.layer_numbers
    assert g["model_sizes"] is kind.model_sizes is not latent.model_sizes
    assert g["judge"] is latent.judge and g["round_ms"] is latent.round_ms


def _bursts(phase: float, until: float, step=0.04, launch=0.34, per=8):
    """The closed loop as the poll sees it: a launch ends with a burst of one
    FIRST token, then ``per`` decode steps of 64 tokens each."""
    t, out = -phase, []
    while t < until:
        t += launch
        out.append((t, 1, 1))
        for _ in range(per):
            t += step
            out.append((t, 64, 0))
    return out


@pytest.mark.parametrize("phase", [0.0, 0.1, 0.2, 0.35, 0.5, 0.62])
def test_the_window_holds_whole_periods_at_any_phase(phase):
    """Whatever the phase at which the ramp ends, the window opens and closes
    at the end of a launch, so tokens a second is that of a whole period; with
    the edges on any burst the same loop reads up to 1 % off."""
    from benchmark.kinds.closed_loop_hybrid_linear import PeriodWindow

    w = PeriodWindow(due=3.0, seconds=30.0)
    edges = [w.see(*b) for b in _bursts(phase, 40.0) if w.close is None]
    assert edges.count("open") == edges.count("close") == 1
    assert 30.0 <= w.close - w.open < 30.0 + 0.34 + 8 * 0.04 + 1e-9
    period = (1 + 8 * 64) / (0.34 + 8 * 0.04)
    assert w.tokens / (w.close - w.open) == pytest.approx(period, rel=1e-9)


def test_the_window_waits_for_a_first_token_and_counts_what_follows_it():
    from benchmark.kinds.closed_loop_hybrid_linear import PeriodWindow

    w = PeriodWindow(due=1.0, seconds=2.0)
    assert w.see(0.5, 64, 1) is None and w.see(1.2, 64, 0) is None
    assert w.see(1.5, 1, 1) == "open" and w.tokens == 0
    assert w.see(2.0, 64, 0) is None and w.see(3.6, 64, 0) is None
    assert w.see(3.7, 1, 1) == "close"
    assert (w.open, w.close, w.tokens) == (1.5, 3.7, 129)


@pytest.mark.parametrize("seed", [0, 7, 3_500_001_001])
def test_folded_prompts_keep_the_multiset_and_fold_every_group(seed):
    from benchmark import loadgen
    from benchmark.kinds.closed_loop_hybrid_linear import folded_prompts

    tr = common.Cell(CELL).traffic
    cycle, strata = tr["cycle"], tr["strata"]
    want = sorted(int(round(x)) for x in
                  loadgen.quantiles(tr["prompt_tokens"], cycle))
    src = folded_prompts(tr, seed, 24576)
    got = [len(next(src)[0]) for _ in range(2 * cycle)]
    assert sorted(got[:cycle]) == sorted(got[cycle:]) == want
    per = cycle // strata
    part = {n: i // per for i, n in enumerate(want)}
    for g in range(0, 2 * cycle, strata):
        assert [part[n] for n in got[g:g + strata]] == [0, 7, 1, 6, 2, 5, 3, 4]
    # the seed's part: which length of a part a group gets
    other = folded_prompts(tr, seed + 1, 24576)
    assert got[:cycle] != [len(next(other)[0]) for _ in range(cycle)]


def test_a_program_without_the_new_leaves_leaves_the_metrics_out():
    """The parent commit has no such family and records no such attributes:
    the numbers the kind computes are left out and the readers return
    None."""
    from benchmark import reducers
    from benchmark.kinds import closed_loop_hybrid_linear as kind

    obs = {"counters": {"decode_steps": 0}, "trace": None, "peaks": None,
           "sizes": sizes_of(config())}
    obs["counters"].update(kind.layer_numbers(obs, [], None))
    cell = common.Cell(CELL)
    for m in cell.per_layer():
        if m["name"].startswith("solar_") and m["source"] != "device_trace":
            assert reducers.read_metric(m["name"], obs, cell.dir) is None


def test_the_kind_reads_its_numbers_from_leaves_and_programs():
    from benchmark import reducers
    from benchmark.kinds import closed_loop_hybrid_linear as kind

    def rec(name, t0, dur, **attrs):
        return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}

    s = sizes_of(config())
    moved = 2 * 64 * counts.state_bytes_per_slot(s)
    recs = [rec("admit", 0.0, 0.001, seated=2, waiting=0)]
    for i in range(4):
        t = i * 0.05
        recs += [rec("decode.dispatch", t, 0.002, kv_positions_read=1000,
                     kv_positions_live=400, state_bytes=moved),
                 rec("decode.device_wait", t + 0.002, 0.02),
                 rec("decode.fetch", t + 0.022, 0.003, expert_assignments=256),
                 rec("decode.emit", t + 0.025, 0.015)]
    obs = {"counters": {"decode_steps": 40, "live_rows_sum": 40 * 64,
                        "live_kv_tokens_sum": 40 * 64 * 4100.0, "bursts": 40,
                        "prefills": 10, "prefill_tokens": 38400},
           "trace": {"programs": {
               "jit__pdecode_fn(1)": {"seconds": 0.16, "launches": 4},
               "jit__prefill_fn(2)": {"seconds": 0.60, "launches": 2}}},
           "peaks": PEAK, "sizes": s, "samples": {}}
    out = kind.layer_numbers(obs, recs, np.array([10, 30, 20, 20] * 5))
    obs["counters"].update(out)
    assert out["kv_positions_read"] / out["kv_positions_live"] == 2.5
    assert out["expert_assignments_decode"] / out["decode_leaves"] == 256
    assert out["expert_load_max_over_mean"] == 1.5
    assert abs(out["emit_ms_a_step"] - 15.0) < 1e-9
    assert abs(out["admit_ms_a_seat"] - 0.5) < 1e-9
    least = counts.decode_step_min_seconds(s, 64, 64 * 4100.0, PEAK,
                                           assignments=32.0)
    assert abs(out["decode_least_s"] - 4 * least["seconds"]) < 1e-12
    assert out["state_bytes_a_step"] == moved
    assert out["least_bytes_a_step"] == least["bytes"]
    assert out["decode_device_s"] == 0.16 and out["prefill_device_s"] == 0.60
    cell = common.Cell(CELL)
    read = {m["name"]: reducers.read_metric(m["name"], obs, cell.dir)
            for m in cell.per_layer() if m["name"].startswith("solar_")}
    assert all(v is not None for v in read.values()), read
    assert 24 < read["solar_state_bytes_share_pct"] < 30
    assert 0 < read["solar_decode_roofline_pct"] < 100
    assert 0 < read["solar_prefill_roofline_pct"] < 100
    assert read["solar_prefill_ms_per_launch"] == pytest.approx(300.0)
    assert read["solar_expert_tokens_per_step"] == 256


# ----------------------------------------------------------------- the walk

def test_rehearsal_of_the_cell_is_correct_and_compiles_nothing_late():
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert r["correct"], rows
    assert r["failed"] == 0 and rows["compiled_in_window"]["value"] == 0
    assert rows["served_logit_gap"]["value"] < 0.01      # float32 on the CPU
    assert r["end_to_end"]["decode_tokens_per_s"] > 0


def test_the_command_walks_the_cell_and_exits_3():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0",
         "--rehearse"], cwd=common.ROOT, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == common.REHEARSAL_EXIT, p.stderr[-2000:]
    # the verdict is the test's above; a second of window beside five other
    # workers may finish too few requests to compare
    assert f"rehearsal of {CELL} done" in p.stderr
    assert '"compared"' in p.stdout
    # ... and a rehearsal never prints a result line
    assert not p.stdout.strip().splitlines()[-1].startswith('{"correct"')


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", ["decay_left_out",
                                   "conv_tail_not_carried_into_decode"])
def test_correct_comes_out_false_for_a_planted_fault(monkeypatch, fault):
    import jax.numpy as jnp

    from pdnlp_tpu.models import hybrid_linear as hl

    if fault == "decay_left_out":
        real = hl._linear_inputs

        def broken(*a, **k):              # alpha = 1: nothing is forgotten
            q, kk, v, g, beta = real(*a, **k)
            return q, kk, v, jnp.zeros_like(g), beta

        monkeypatch.setattr(hl, "_linear_inputs", broken)
    else:
        real = hl.linear_prompt

        def broken(*a, **k):   # the first decode step convolves over zeros
            y, S, tail = real(*a, **k)
            return y, S, jnp.zeros_like(tail)

        monkeypatch.setattr(hl, "linear_prompt", broken)
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert not r["correct"]
    assert not (rows["served_logit_gap"]["ok"]
                and rows["routing_swap_share"]["ok"]), rows


@pytest.mark.parametrize("seed", [3, 11, 2 ** 31 + 5])
def test_the_fp8_control_fails_the_limits_and_bfloat16_lies_far_below_it(seed):
    """The reference computed in fp8 in the program's place, judged by the
    configuration's OWN limits as the rehearsal reads them (the gap's and
    the margin's are the cell's; at 3 experts of 8 a token most positions
    are near ties, so the rehearsal asks for fewer clear tokens and allows
    more swaps): it fails by the gap AND by the swaps.  The same in bfloat16
    — the precision the configuration states for the matmuls — swaps within
    the limit and its widest gap is a fraction of fp8's (at 128 wide the
    gap itself reads up to 0.16: the chip run at the published widths is
    where bfloat16 is held to the limit, PERF.md section 2)."""
    from benchmark.kinds import closed_loop_hybrid_linear as kind

    cfg = config()
    sizes = sizes_of(cfg, rehearse=True)
    limits = {**cfg["check"], **cfg["rehearse"]["check"]}
    rng = np.random.default_rng(seed)
    served = [(rng.integers(5, 1000, 48).tolist(),
               rng.integers(5, 1000, 40).tolist()) for _ in range(5)]
    rows = {}
    for prec in ("bf16", "fp8"):
        gaps, margins = kind.reference_gaps(served, seed, sizes, (3,),
                                            lowprec=prec)
        checks = common.Checks()
        kind.judge(checks, [list(zip(gs, ms))
                            for gs, ms in zip(gaps, margins)], limits)
        rows[prec] = {r["check"]: r for r in checks.rows}
    bf16, fp8 = rows["bf16"], rows["fp8"]
    assert not fp8["served_logit_gap"]["ok"], rows
    assert not fp8["routing_swap_share"]["ok"], rows
    assert bf16["routing_swap_share"]["ok"], rows
    assert bf16["served_tokens_compared"]["ok"], rows
    assert bf16["served_logit_gap"]["value"] \
        < fp8["served_logit_gap"]["value"] / 3, rows


def test_the_state_control_holds_the_state_in_bfloat16_between_positions():
    """``state-bf16`` leaves the matmuls' operands alone and rounds the
    recurrent state after every position: the reference's logits move."""
    from benchmark.kinds import closed_loop_hybrid_linear as kind
    from benchmark.reference import solar_open2 as ref

    assert kind._precisions("state-bf16") == ("f32", "bf16")
    assert kind._precisions("fp8") == ("fp8", "f32")
    sizes = sizes_of(config(), rehearse=True)
    seq = np.random.default_rng(1).integers(5, 1000, 64).tolist()
    (a, _), = ref.forward(3, sizes, [seq])
    (b, _), = ref.forward(3, sizes, [seq], state="bf16")
    d = float(np.abs(np.asarray(a) - np.asarray(b)).max())
    assert 1e-4 < d < 0.5
