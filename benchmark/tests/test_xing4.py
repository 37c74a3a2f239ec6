"""The four-stream configuration (manifold-constrained hyper-connections
around MLA, 64 experts held whole behind a bias-corrected router, the whole
vocabulary) and the cell this PR adds, on the CPU: counts from shapes
against a hand count, the configuration file against the catalog row, the
cell found as data, the kind's loop and numbers, a ``--rehearse`` walk,
``correct`` coming out false for planted faults, and the controls."""
import argparse
import json

import numpy as np
import pytest

from benchmark import common, counts_axk1, counts_xing4 as counts
from benchmark.run import run_cell

CONFIG = "xing4-29b-ep1-stage"
CELL = "xing4-decode-longdoc-saturated"
LATENT_CELL = "axk1-decode-longdoc-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "hbm_bytes": 16e9}
REDUCED = {"num_hidden_layers": (40, 6), "first_k_dense_replace": (2, 1),
           "num_nextn_predict_layers": (1, 0)}


def config():
    return common.load_json(common.HERE, "configs", CONFIG + ".json")


def sizes_of(cfg, rehearse=False):
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if rehearse:
        sizes.update(cfg["rehearse"]["sizes"])
    sizes["rope_scaling"] = cfg["rope_scaling"]
    return sizes


def rehearse(workload, seed=5, seconds=2.0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=0, rehearse=True)
    assert run_cell(ns) == common.REHEARSAL_EXIT
    return ns.result


# ------------------------------------------------------ counts from shapes

def test_parameters_from_the_published_widths_are_issue_37s_table():
    s = sizes_of(config())
    p = counts.parts(s)
    # MLA 28.41 M = 2.75 + 4.72 + 2.06 + 4.19 + 14.68 (+ norms)
    assert p["attention"] / 1e6 == pytest.approx(28.41, abs=0.01)
    assert p["dense_ffn"] == 3 * 3584 * 9216
    assert p["expert"] == p["shared"] == 3 * 3584 * 1024
    assert p["router"] == 3584 * 64 and p["router_bias"] == 64
    # the mixing: two sub-layers a layer, phi [4 * 3584, 4 + 4 + 16], b, a
    assert counts.mixing_params(s) == 4 * 3584 * 24 + 24 + 3
    assert p["mixing"] == 2 * counts.mixing_params(s) == 688_182
    assert counts.layers(s) == (1, 5)
    dense = p["attention"] + p["dense_ffn"] + p["mixing"]
    moe = (p["attention"] + p["shared"] + p["router"] + p["router_bias"]
           + p["mixing"] + 64 * p["expert"])
    assert dense / 1e6 == pytest.approx(128.2, abs=0.05)
    assert moe / 1e6 == pytest.approx(745.0, abs=0.05)
    assert counts.params_held(s) == dense + 5 * moe + 2 * 131072 * 3584 + 3584
    assert counts.params_held(s) / 1e9 == pytest.approx(4.793, abs=0.0005)
    assert counts.cache_bytes_per_token(s) == 6 * 576 * 2 == 6912
    assert counts.stream_bytes_per_token(s) == 4 * 3584 * 2
    assert counts.expected_assignments(s, 128) == 512.0     # all are held


def test_the_programs_own_count_agrees():
    from pdnlp_tpu.models import get_config, latent_moe

    cfg = get_config(config()["program"]["model"])
    assert latent_moe.param_count(cfg) == counts.params_held(sizes_of(config()))


def test_counts_at_the_tiny_size_against_a_hand_count():
    """``xing4-stage-tiny``: 4 streams of 128, 1 dense + 2 expert layers, 8
    experts all held, 20 Sinkhorn steps — every term written out."""
    s = sizes_of(config(), rehearse=True)
    n, C, L = 4, 128, 3
    assert counts.mixing_params(s) == n * C * 24 + 24 + 3 == 12315
    assert counts.added_params(s) == L * 2 * 12315 + 2 * 8
    assert counts.params_held(s) == counts_axk1.params_held(s) \
        + counts.added_params(s)
    # bytes a token: 6 sub-layers x (read + write) x 4 x 128 values x 2 B
    assert counts.mixing_bytes_per_token(s) == 6 * 2 * 4 * 128 * 2 == 12288
    one = (2 * n * C              # norm statistic
           + 2 * n * C * 24       # phi products
           + 2 * n * C            # H_pre @ X
           + 2 * n * (n + 1) * C  # H_res @ X + outer(H_post, y)
           + 20 * 2 * 2 * n * n)  # 20 x 2 normalisations of 4 x 4
    assert one == 1024 + 24576 + 1024 + 5120 + 1280
    assert counts.mixing_flops_per_token(s) == 6 * one
    rows, live = 4.0, 4 * 50.0
    base = counts_axk1.decode_step_min_seconds(s, rows, live, PEAK)
    step = counts.decode_step_min_seconds(s, rows, live, PEAK)
    assert step["mixing_bytes"] == counts.added_params(s) * 2 + rows * 12288
    assert step["bytes"] == base["bytes"] + step["mixing_bytes"]
    assert step["flops"] == base["flops"] + rows * 6 * one
    pre = counts.prefill_min_seconds(s, 30.0, PEAK)
    assert pre["bytes"] - counts_axk1.prefill_min_seconds(s, 30.0, PEAK)["bytes"] \
        == counts.added_params(s) * 2 + 30 * 12288


def test_a_decode_step_is_bound_by_bytes_and_a_prompt_by_flops():
    s = sizes_of(config())
    step = counts.decode_step_min_seconds(s, 128, 128 * 2300, PEAK)
    weights = (counts.params_held(s) - 131072 * 3584 + 128 * 3584) * 2
    streams = 128 * 12 * 2 * 4 * 3584 * 2
    assert step["bound"] == "bytes"
    assert step["bytes"] == weights + (128 * 2300 + 128) * 6912 + streams
    # ISSUE 37: 8.65 GB of weights a step; the mixing about 1 % of the bytes
    assert weights / 1e9 == pytest.approx(8.65, abs=0.01)
    assert 0.007 < step["mixing_bytes"] / step["bytes"] < 0.012
    assert 0.0125 < step["seconds"] < 0.0140
    pre = counts.prefill_min_seconds(s, 2048, PEAK)
    assert pre["bound"] == "flops" and 0.011 < pre["seconds"] < 0.015


# ------------------------------------------------------- the cell as data

def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` under the same key, the
    three ``reduced`` ones changed and nothing else."""
    try:
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
    except OSError:
        pytest.skip("the catalog is not on this machine")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in REDUCED:
            assert (v, cfg[k]) == REDUCED[k], k
        else:
            assert cfg[k] == v, k
    assert cfg["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == row["source_url"]
    # whole: the 64 experts and the 131 072 rows are as published
    assert (cfg["n_routed_experts"], cfg["vocab_size"], cfg["ep_size"]) \
        == (64, 131072, 1)
    for key in ("source", "deployment", "precision", "assumed", "check",
                "rehearse", "program", "published"):
        assert cfg[key], key
    a = cfg["assumed"]
    assert a["slots"] * a["max_len"] == a["pool_pages"] * a["page_size"]
    for item in ("mhc_norm", "hc_eps", "mhc_clamp", "sinkhorn_order",
                 "mhc_scalars", "streams", "seeded_ranges", "topk_method",
                 "head_dim", "dtypes", "why"):
        assert a[item], item


def test_the_new_cell_is_found_and_reports_what_it_says():
    cell = common.Cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["kind"] == "closed_loop_latent_mhc"
    assert [m["name"] for m in cell.end_to_end()] == ["decode_tokens_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in cell.per_layer()}
    latent = {m["name"] for m in common.Cell(LATENT_CELL).per_layer()}
    # the seven shared ``.json`` readers the latent cell reads
    assert names & latent == {m for m in latent if m.startswith("sat_")}
    assert len(names & latent) == 7
    own = names - latent
    assert len(own) == 11 and all(n.startswith("xing_") for n in own)
    assert sum("roofline" in n for n in own) == 2
    # one traffic shape for two configurations of one family
    tr, other = dict(cell.traffic), dict(common.Cell(LATENT_CELL).traffic)
    for t in (tr, other):
        del t["kind"], t["why"]
    assert tr == other
    assert tr["clients"] == cell.config["assumed"]["slots"] == tr["cycle"] == 128
    assert (tr["prompt_tokens"]["hi"] + tr["new_tokens"]
            <= cell.config["assumed"]["max_len"])


def test_the_loop_is_the_period_loops_code_over_this_kinds_parts():
    from benchmark.kinds import closed_loop_hybrid_linear as periods
    from benchmark.kinds import closed_loop_latent_mhc as kind
    from benchmark.kinds import closed_loop_latent_moe as latent

    assert kind.run.__code__ is periods.run.__code__
    g = kind.run.__globals__
    assert g["build"] is kind.build and g["compare"] is kind.compare
    assert g["control"] is kind.control
    assert g["layer_numbers"] is kind.layer_numbers
    assert g["model_sizes"] is latent.model_sizes
    assert g["PeriodWindow"] is periods.PeriodWindow
    assert g["folded_prompts"] is periods.folded_prompts
    # ... and the module the code came from still calls its own
    assert periods.run.__globals__["build"] is periods.build


def test_the_vocabulary_file_holds_every_row_once():
    from benchmark.kinds import closed_loop_latent_mhc as kind

    lines = kind.vocab_lines(131072)
    assert len(lines) == len(set(lines)) == 131072
    assert lines[:5] == ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    assert kind.vocab_lines(1000) == kind.loadgen.vocab_lines(1000)


def test_a_program_without_the_new_leaves_leaves_the_metrics_out():
    from benchmark import reducers
    from benchmark.kinds import closed_loop_latent_mhc as kind

    obs = {"counters": {"decode_steps": 0}, "trace": None, "peaks": None,
           "sizes": sizes_of(config())}
    obs["counters"].update(kind.layer_numbers(obs, [], None))
    cell = common.Cell(CELL)
    for m in cell.per_layer():
        if m["name"].startswith("xing_") and m["source"] != "device_trace":
            assert reducers.read_metric(m["name"], obs, cell.dir) is None


def test_the_kind_reads_its_numbers_from_leaves_and_programs():
    from benchmark import reducers
    from benchmark.kinds import closed_loop_latent_mhc as kind

    def rec(name, t0, dur, **attrs):
        return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}

    s = sizes_of(config())
    recs = [rec("admit", 0.0, 0.001, seated=2, waiting=0)]
    for i in range(4):
        t = i * 0.05
        recs += [rec("decode.dispatch", t, 0.002, kv_positions_read=1000,
                     kv_positions_live=400),
                 rec("decode.device_wait", t + 0.002, 0.02),
                 rec("decode.fetch", t + 0.022, 0.003, expert_assignments=2560),
                 rec("decode.emit", t + 0.025, 0.015)]
    obs = {"counters": {"decode_steps": 40, "live_rows_sum": 40 * 128,
                        "live_kv_tokens_sum": 40 * 128 * 2300.0, "bursts": 40,
                        "prefills": 10, "prefill_tokens": 20480},
           "trace": {"programs": {
               "jit__pdecode_fn(1)": {"seconds": 0.16, "launches": 4},
               "jit__prefill_fn(2)": {"seconds": 0.12, "launches": 2}}},
           "peaks": PEAK, "sizes": s, "samples": {}}
    out = kind.layer_numbers(obs, recs, np.array([10, 30, 20, 20] * 16))
    obs["counters"].update(out)
    assert out["kv_positions_read"] / out["kv_positions_live"] == 2.5
    assert out["expert_assignments_decode"] / out["decode_leaves"] == 2560
    assert out["expert_load_max_over_mean"] == 1.5
    assert abs(out["emit_ms_a_step"] - 15.0) < 1e-9
    least = counts.decode_step_min_seconds(s, 128, 128 * 2300.0, PEAK,
                                           assignments=512.0)
    assert abs(out["decode_least_s"] - 4 * least["seconds"]) < 1e-12
    assert out["mhc_bytes_a_step"] == least["mixing_bytes"]
    assert out["least_bytes_a_step"] == least["bytes"]
    assert out["decode_device_s"] == 0.16 and out["prefill_device_s"] == 0.12
    cell = common.Cell(CELL)
    read = {m["name"]: reducers.read_metric(m["name"], obs, cell.dir)
            for m in cell.per_layer() if m["name"].startswith("xing_")}
    assert all(v is not None for v in read.values()), read
    assert 0.7 < read["xing_mhc_bytes_share_pct"] < 1.2
    assert 0 < read["xing_decode_roofline_pct"] < 100
    assert 0 < read["xing_prefill_roofline_pct"] < 100
    assert read["xing_prefill_ms_per_launch"] == pytest.approx(60.0)
    assert read["xing_expert_tokens_per_step"] == 2560


# ----------------------------------------------------------------- the walk

def test_rehearsal_of_the_cell_is_correct_and_compiles_nothing_late():
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert r["correct"], rows
    assert r["failed"] == 0 and rows["compiled_in_window"]["value"] == 0
    assert rows["served_logit_gap"]["value"] < 0.01      # float32 on the CPU
    assert r["end_to_end"]["decode_tokens_per_s"] > 0


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", ["h_res_left_the_identity",
                                   "selection_bias_left_out"])
def test_correct_comes_out_false_for_a_planted_fault(monkeypatch, fault):
    import jax.numpy as jnp

    from pdnlp_tpu.models import hyper_connections as hc, latent_moe as lm

    if fault == "h_res_left_the_identity":
        real = hc.coefficients

        def broken(x, p, cfg):           # the streams are never mixed
            pre, post, res = real(x, p, cfg)
            eye = jnp.eye(res.shape[0])[:, :, None]
            return pre, post, jnp.broadcast_to(eye, res.shape)

        monkeypatch.setattr(hc, "coefficients", broken)
    else:
        real = lm.route

        def broken(f, router, cfg, dtype, bias=None):   # chosen by score
            return real(f, router, cfg, dtype, None)

        monkeypatch.setattr(lm, "route", broken)
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert not r["correct"]
    assert not (rows["served_logit_gap"]["ok"]
                and rows["routing_swap_share"]["ok"]), rows


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_the_fp8_control_fails_the_limits_and_bfloat16_lies_far_below_it(seed):
    """The reference computed in fp8 in the program's place, judged by the
    configuration's OWN limits as the rehearsal reads them (at 128 wide and
    3 experts of 8 the rehearsal asks for fewer clear tokens, allows fewer
    swaps and a narrower gap, 0.05: float32 on the CPU reads under 0.01
    there, a planted fault 0.10 and more, fp8 0.5-0.6): it fails by the gap.  The same in bfloat16 — the precision the configuration states for
    the matmuls — reads a fraction of fp8's gap; the mixing alone in
    bfloat16 (``mix-bf16``) moves the logits and lies below both (the chip
    run at the published widths is where each is held to the limits,
    PERF.md section 2)."""
    from benchmark.kinds import closed_loop_latent_mhc as kind

    assert kind._precisions("mix-bf16") == ("f32", "bf16")
    assert kind._precisions("fp8") == ("fp8", "f32")
    cfg = config()
    sizes = sizes_of(cfg, rehearse=True)
    limits = {**cfg["check"], **cfg["rehearse"]["check"]}
    rng = np.random.default_rng(seed)
    served = [(rng.integers(5, 1000, 48).tolist(),
               rng.integers(5, 1000, 40).tolist()) for _ in range(5)]
    rows, widest = {}, {}
    for prec in ("mix-bf16", "bf16", "fp8"):
        gaps, margins = kind.reference_gaps(served, seed, sizes, (3,),
                                            lowprec=prec)
        checks = common.Checks()
        kind.judge(checks, [list(zip(gs, ms))
                            for gs, ms in zip(gaps, margins)], limits)
        rows[prec] = {r["check"]: r for r in checks.rows}
        widest[prec] = max(g for gs in gaps for g in gs)
    assert not rows["fp8"]["served_logit_gap"]["ok"], rows
    assert rows["bf16"]["served_tokens_compared"]["ok"], rows
    assert rows["bf16"]["served_logit_gap"]["value"] \
        < rows["fp8"]["served_logit_gap"]["value"] / 3, rows
    assert 0 < widest["mix-bf16"] < widest["fp8"], widest
