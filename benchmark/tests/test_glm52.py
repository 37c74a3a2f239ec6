"""The learned-sparse-attention configuration (a lightning indexer whose
picks the ``shared`` layers reuse, over MLA and 16 of 256 experts; a second
pool of index keys) and the cell this PR adds, on the CPU: counts from
shapes against a hand count, the configuration file against the catalog
row and the program's preset, the cell found as data, the kind's loop and
numbers, a ``--rehearse`` walk in which the selection binds, and the
controls — ``sel-recent`` among them — coming out not ``correct``."""
import argparse
import dataclasses
import json

import numpy as np
import pytest

from benchmark import common, counts_glm52 as counts
from benchmark.run import run_cell

CONFIG = "glm-5.2-ep16-share"
CELL = "glm52-decode-longdoc8k-saturated"
LATENT_CELL = "xing4-decode-longdoc-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "hbm_bytes": 16e9}
HELD = ["full", "shared", "shared", "shared", "full", "shared", "shared"]
REDUCED = {"num_hidden_layers": (78, 7), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 16), "vocab_size": (154880, 19360),
           "num_nextn_predict_layers": (1, 0)}
LISTS = ("indexer_types", "mlp_layer_types")


def config():
    return common.load_json(common.HERE, "configs", CONFIG + ".json")


def sizes_of(cfg, rehearse=False):
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    kinds = cfg["indexer_types"]
    if rehearse:
        sizes.update(cfg["rehearse"]["sizes"])
        kinds = cfg["rehearse"]["indexer_types"]
    sizes["rope_parameters"] = cfg["rope_parameters"]
    sizes["indexer_types"] = list(kinds)[:sizes["num_hidden_layers"]]
    return sizes


def rehearse(workload, seed=5, seconds=2.0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=0, rehearse=True)
    assert run_cell(ns) == common.REHEARSAL_EXIT
    return ns.result


# ------------------------------------------------------ counts from shapes

def test_parameters_from_the_published_widths_are_issue_43s_table():
    s = sizes_of(config())
    p = counts.parts(s)
    # MLA 165.02 M = 12.58 + 33.55 + 3.54 + 14.68 + 100.66 (+ norms)
    assert p["attention"] / 1e6 == pytest.approx(165.02, abs=0.02)
    # an indexer 9.37 M = [2048, 32 x 128] + [6144, 128] (+ norm) + [6144, 32]
    assert p["indexer"] == 2048 * 4096 + 6144 * 128 + 2 * 128 + 6144 * 32
    assert p["indexer"] / 1e6 == pytest.approx(9.37, abs=0.01)
    assert p["dense_ffn"] == 3 * 6144 * 12288
    assert p["expert"] == p["shared"] == 3 * 6144 * 2048
    assert p["router"] == 6144 * 256 + 256
    assert counts.layers(s) == (1, 6, 2)
    dense = p["attention"] + p["indexer"] + p["dense_ffn"]
    shared = p["attention"] + p["shared"] + p["router"] + 16 * p["expert"]
    assert dense / 1e6 == pytest.approx(400.9, abs=0.05)
    assert shared / 1e6 == pytest.approx(808.3, abs=0.05)
    assert (shared + p["indexer"]) / 1e6 == pytest.approx(817.7, abs=0.05)
    assert counts.params_held(s) == dense + 6 * shared + p["indexer"] \
        + 2 * 19360 * 6144 + 6144
    assert counts.params_held(s) / 1e9 == pytest.approx(5.498, abs=0.0005)
    # 7 x 576 latent values and 2 x 128 index values a token, 2 bytes each
    assert counts.latent_bytes_per_token(s) == 7 * 576 * 2 == 8064
    assert counts.index_bytes_per_token(s) == 2 * 128 * 2 == 512
    assert counts.expected_assignments(s, 32) == 16.0


def test_the_programs_own_count_agrees():
    from pdnlp_tpu.models import get_config, latent_moe

    cfg = get_config(config()["program"]["model"])
    assert latent_moe.param_count(cfg) == counts.params_held(sizes_of(config()))


def test_counts_at_the_tiny_size_against_a_hand_count():
    """``glm52-share-tiny``: hidden 128, 4 heads of 24 + 8 / 32, ranks 48 /
    32, an indexer of 4 heads of 16 that picks 16, layers full, shared,
    shared, full, shared, 4 of 8 experts held, 3 a token — every term
    written out."""
    s = sizes_of(config(), rehearse=True)
    H, N, qr, kr, dn, dr, dv = 128, 4, 48, 32, 24, 8, 32
    attn = (H * qr + qr * N * (dn + dr) + H * (kr + dr) + kr * N * (dn + dv)
            + N * dv * H + 2 * H + qr + kr)
    index = qr * 4 * 16 + H * 16 + 2 * 16 + H * 4
    expert, router = 3 * H * 64, H * 8 + 8
    p = counts.parts(s)
    assert (p["attention"], p["indexer"], p["expert"], p["router"]) \
        == (attn, index, expert, router)
    assert counts.layers(s) == (1, 4, 2)
    held = (attn + 3 * H * 256 + 4 * (attn + router + expert + 4 * expert)
            + 2 * index + 2 * 1000 * H + H)
    assert counts.params_held(s) == held
    assert counts.cache_bytes_per_token(s) == (5 * 40 + 2 * 16) * 2
    # a prompt of 30: the first 16 queries pick all they see, the rest 16
    assert counts.picked_pairs(s, 30) == 16 * 17 / 2 + 14 * 16 == 360
    assert counts.picked_pairs(s, 10) == 55
    # a decode step of 4 rows at contexts of 50: 4 x 16 picked, 200 live
    rows, live = 4.0, 200.0
    step = counts.decode_step_min_seconds(s, rows, live, PEAK)
    a = rows * 3 * 4 / 8                      # assignments to held experts
    touched = 4 * (1 - (3 / 4) ** a)
    weights = held - 4 * (4 - touched) * expert - 1000 * H + rows * H
    assert step["index_bytes"] == live * 2 * 16 * 2
    assert step["bytes"] == pytest.approx(
        weights * 2 + live * 64 + 64 * 5 * 40 * 2 + rows * (5 * 40 + 32) * 2)
    per_token = 2.0 * (5 * attn + 2 * index + 3 * H * 256
                       + 4 * (router + expert + a / rows * expert))
    assert step["flops"] == pytest.approx(
        rows * (per_token + 2.0 * H * 1000) + live * 2 * 4 * 16 * 2.0
        + 64 * 5 * N * ((kr + dr) + kr) * 2.0)
    # given the counted picks, the default's place is taken
    more = counts.decode_step_min_seconds(s, rows, live, PEAK, picked=40.0)
    assert step["bytes"] - more["bytes"] == 24 * 5 * 40 * 2
    pre = counts.prefill_min_seconds(s, 30.0, PEAK)
    a = 30 * 3 * 4 / 8
    per_token = 2.0 * (5 * attn + 2 * index + 3 * H * 256
                       + 4 * (router + expert + a / 30 * expert))
    assert pre["flops"] == pytest.approx(
        30 * per_token + 0.5 * 900 * 2 * 4 * 16 * 2.0
        + 360 * 5 * N * (dn + dr + dv) * 2.0 + 2.0 * H * 1000)
    assert pre["bytes"] == (held - 1000 * H + 30 * H) * 2 + 30 * 464


def test_a_decode_step_is_bound_by_bytes_and_a_prompt_by_flops():
    s = sizes_of(config())
    step = counts.decode_step_min_seconds(s, 32, 32 * 6144.0, PEAK)
    assert step["bound"] == "bytes"
    # ISSUE 43 reckoned 3.6 GB outside the experts (3.51 without the
    # embedding, of which a step reads 32 rows), up to 4.5-4.7 GB of the experts
    # a row chose, 0.5-0.6 GB of picked latents, 0.1 GB of index keys
    p = counts.parts(s)
    outside = (counts.params_held(s) - 6 * 16 * p["expert"]
               - 19360 * 6144) * 2
    assert outside / 1e9 == pytest.approx(3.51, abs=0.02)
    assert step["index_bytes"] / 1e9 == pytest.approx(0.10, abs=0.005)
    assert 32 * 2048 * 8064 / 1e9 == pytest.approx(0.53, abs=0.01)
    assert 0.0100 < step["seconds"] < 0.0120
    assert 0.010 < step["index_bytes"] / step["bytes"] < 0.013
    # every latent of the contexts would be three times that: the
    # selection's point
    assert 32 * 6144 * 8064 == 3 * 32 * 2048 * 8064
    pre = counts.prefill_min_seconds(s, 5888, PEAK)
    assert pre["bound"] == "flops" and 0.12 < pre["seconds"] < 0.14


# ------------------------------------------------------- the cell as data

def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` under the same key, the
    seven ``reduced`` ones changed — the two lists to the published lists'
    entries 2-8 — and nothing else."""
    try:
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-5.2")
    except OSError:
        pytest.skip("the catalog is not on this machine")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in REDUCED:
            assert (v, cfg[k]) == REDUCED[k], k
        elif k in LISTS:
            assert len(v) == 78 and cfg[k] == v[2:9], k
        else:
            assert cfg[k] == v, k
    assert cfg["indexer_types"] == HELD
    # the two scalar keys restate the published list
    pattern = ["full" if l < 3 or (l - 2) % 4 == 0 else "shared"
               for l in range(78)]
    assert row["config"]["indexer_types"] == pattern
    assert (cfg["index_topk_freq"], cfg["index_skip_topk_offset"]) == (4, 3)
    assert {k: cfg["published"][k] for k in REDUCED} \
        == {k: v[0] for k, v in REDUCED.items()}
    assert sorted(cfg["reduced"]) == sorted(list(REDUCED) + list(LISTS))
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["source"] == row["source_url"]
    # the share: 16 of the router's 256, an eighth of the vocabulary
    assert (cfg["router_width"], cfg["expert_first"]) == (256, 0)
    assert cfg["vocab_size"] * 8 == 154880
    for key in ("source", "deployment", "precision", "assumed", "check",
                "rehearse", "program", "published"):
        assert cfg[key], key
    assert "16 chips share each layer" in cfg["deployment"]
    a = cfg["assumed"]
    assert a["slots"] * a["max_len"] == a["pool_pages"] * a["page_size"]
    for item in ("index_key_norm", "indexer_inputs", "shared_layers",
                 "indexer_types_key", "not_consumed", "ties", "index_cache",
                 "seeded_ranges", "head_dim", "rope", "topk_method",
                 "dtypes", "why"):
        assert a[item], item
    # a rehearsal SELECTS: its prompts are longer than its index_topk
    r = cfg["rehearse"]
    assert r["sizes"]["index_topk"] < r["traffic"]["prompt_tokens"]["lo"]


def test_the_big_preset_is_the_configuration_files_numbers_one_by_one():
    """A default left standing in ``LatentMoEConfig`` would be A.X-K1's:
    every field of the preset against the file's published key."""
    from pdnlp_tpu.models import get_config

    cfg = config()
    pre = get_config(cfg["program"]["model"])
    want = {
        "vocab_size": cfg["vocab_size"], "hidden_size": cfg["hidden_size"],
        "num_layers": cfg["num_hidden_layers"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "num_heads": cfg["num_attention_heads"],
        "q_lora_rank": cfg["q_lora_rank"], "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "n_routed_experts": cfg["router_width"],
        "experts_held": cfg["n_routed_experts"],
        "expert_first": cfg["expert_first"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "n_shared_experts": cfg["n_shared_experts"],
        "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
        # rope_type default: no scaling; the yarn fields are not read
        "rope_factor": 1.0, "rope_original_max": cfg["max_position_embeddings"],
        "rope_beta_fast": 32.0, "rope_beta_slow": 1.0, "rope_mscale": 1.0,
        "rope_mscale_all_dim": 1.0,
        "max_position": cfg["max_position_embeddings"],
        # one residual stream: the mixing's fields are not read
        "hc_mult": 1, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "hc_res_clamp": (-30.0, 30.0),
        "selection_bias": cfg["topk_method"] == "noaux_tc",
        "index_n_heads": cfg["index_n_heads"],
        "index_head_dim": cfg["index_head_dim"],
        "index_topk": cfg["index_topk"],
        "indexer_types": tuple(cfg["indexer_types"]),
        "weight_dtype": "bfloat16",
    }
    assert cfg["rope_parameters"]["rope_type"] == "default"
    assert cfg["qk_head_dim"] == pre.qk_nope_head_dim + pre.qk_rope_head_dim
    assert set(want) == {f.name for f in dataclasses.fields(pre)}
    for k, v in want.items():
        assert getattr(pre, k) == v, k
    # and the tiny preset is the rehearsal's sizes
    tiny = get_config(cfg["rehearse"]["program"]["model"])
    r = cfg["rehearse"]["sizes"]
    for field, key in (("hidden_size", "hidden_size"),
                       ("num_heads", "num_attention_heads"),
                       ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("v_head_dim", "v_head_dim"),
                       ("experts_held", "n_routed_experts"),
                       ("n_routed_experts", "router_width"),
                       ("index_n_heads", "index_n_heads"),
                       ("index_head_dim", "index_head_dim"),
                       ("index_topk", "index_topk"),
                       ("num_layers", "num_hidden_layers")):
        assert getattr(tiny, field) == r[key], field
    assert list(tiny.indexer_types) == cfg["rehearse"]["indexer_types"]


def test_the_new_cell_is_found_and_reports_what_it_says():
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    assert len(b["workloads"]) == 7 and b["workloads"][-1]["name"] == CELL
    assert not [w for w in b["workloads"] if w["chips"] != 1]
    cell = common.Cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["kind"] == "closed_loop_latent_dsa"
    assert [m["name"] for m in cell.end_to_end()] == ["decode_tokens_per_s",
                                                      "setup_s"]
    names = {m["name"] for m in cell.per_layer()}
    latent = {m["name"] for m in common.Cell(LATENT_CELL).per_layer()}
    # the seven shared ``.json`` readers the latent cells read
    assert names & latent == {m for m in latent if m.startswith("sat_")}
    assert len(names & latent) == 7
    own = names - latent
    assert len(own) == 12 and all(n.startswith("glm_") for n in own)
    assert sum("roofline" in n for n in own) == 2
    tr, a = cell.traffic, cell.config["assumed"]
    assert tr["clients"] == a["slots"] == tr["cycle"] == 32
    assert tr["prompt_tokens"] == {"dist": "uniform", "lo": 4096, "hi": 7680}
    assert tr["prompt_tokens"]["hi"] + tr["new_tokens"] <= a["max_len"]
    assert tr["buckets"][-1] == tr["prompt_tokens"]["hi"]
    # every context lies past the selection's budget: nothing in the window
    # runs the dense path
    assert tr["prompt_tokens"]["lo"] >= 2 * cell.config["index_topk"]
    assert (tr["check_requests"], tr["strata"], tr["ramp_s"]) == (16, 8, 30.0)


def test_the_loop_is_the_period_loops_code_over_this_kinds_parts():
    from benchmark.kinds import closed_loop_hybrid_linear as periods
    from benchmark.kinds import closed_loop_latent_dsa as kind
    from benchmark.kinds import closed_loop_latent_mhc as mhc

    assert kind.run.__code__ is periods.run.__code__
    g = kind.run.__globals__
    assert g["build"] is kind.build and g["compare"] is kind.compare
    assert kind.build.__code__ is mhc.build.__code__
    assert kind.build.__globals__["make_weights"] is kind.make_weights
    assert g["control"] is kind.control
    assert g["layer_numbers"] is kind.layer_numbers
    assert g["model_sizes"] is kind.model_sizes
    assert g["PeriodWindow"] is periods.PeriodWindow
    assert g["folded_prompts"] is periods.folded_prompts
    assert periods.run.__globals__["build"] is periods.build
    sizes = kind.model_sizes(common.Cell(CELL), False)
    assert sizes["indexer_types"] == HELD
    assert sizes["rope_parameters"]["rope_theta"] == 8000000
    tiny = kind.model_sizes(common.Cell(CELL), True)
    assert tiny["indexer_types"] == ["full", "shared", "shared", "full",
                                     "shared"]


def test_a_program_without_the_new_leaves_leaves_the_metrics_out():
    """The parent's program (no ``positions_*`` on its fetch leaves) under
    this PR's benchmark files: every reader returns nothing, none raises."""
    from benchmark import reducers
    from benchmark.kinds import closed_loop_latent_dsa as kind

    obs = {"counters": {"decode_steps": 0}, "trace": None, "peaks": None,
           "sizes": sizes_of(config())}
    obs["counters"].update(kind.layer_numbers(obs, [], None))
    cell = common.Cell(CELL)
    for m in cell.per_layer():
        if m["name"].startswith("glm_") and m["source"] != "device_trace":
            assert reducers.read_metric(m["name"], obs, cell.dir) is None
    # leaves of a program that counts no picks: the selection's three stay out
    recs = [{"name": "decode.dispatch", "t0": 0.0, "dur": 0.001,
             "attrs": {"kv_positions_read": 10, "kv_positions_live": 5}},
            {"name": "decode.fetch", "t0": 0.001, "dur": 0.001,
             "attrs": {"expert_assignments": 4}}]
    obs = {"counters": {"decode_steps": 1, "live_rows_sum": 1,
                        "live_kv_tokens_sum": 5.0, "bursts": 1},
           "trace": None, "peaks": PEAK, "sizes": sizes_of(config())}
    obs["counters"].update(kind.layer_numbers(obs, recs, None))
    for name in ("glm_picked_share_pct", "glm_kv_read_amplification",
                 "glm_index_bytes_share_pct"):
        assert reducers.read_metric(name, obs, cell.dir) is None, name


def test_the_kind_reads_its_numbers_from_leaves_and_programs():
    from benchmark import reducers
    from benchmark.kinds import closed_loop_latent_dsa as kind

    def rec(name, t0, dur, **attrs):
        return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}

    s = sizes_of(config())
    recs = [rec("admit", 0.0, 0.001, seated=2, waiting=0),
            rec("prefill.fetch", 0.0, 0.001, positions_visible=18_000_000,
                positions_picked=10_000_000, expert_assignments=20000)]
    for i in range(4):
        t = i * 0.05
        recs += [rec("decode.dispatch", t, 0.002, kv_positions_read=32 * 2048,
                     kv_positions_live=32 * 6144),
                 rec("decode.device_wait", t + 0.002, 0.02),
                 rec("decode.fetch", t + 0.022, 0.003, expert_assignments=96,
                     positions_visible=32 * 6144, positions_picked=31 * 2048),
                 rec("decode.emit", t + 0.025, 0.015)]
    obs = {"counters": {"decode_steps": 40, "live_rows_sum": 40 * 31,
                        "live_kv_tokens_sum": 40 * 31 * 6144.0, "bursts": 40,
                        "prefills": 10, "prefill_tokens": 58880},
           "trace": {"programs": {
               "jit__pdecode_fn(1)": {"seconds": 0.09, "launches": 4},
               "jit__prefill_fn(2)": {"seconds": 0.9, "launches": 2}}},
           "peaks": PEAK, "sizes": s, "samples": {}}
    out = kind.layer_numbers(obs, recs, np.array([10, 30, 20, 20] * 4))
    obs["counters"].update(out)
    assert out["positions_picked_decode"] == 4 * 31 * 2048
    assert out["positions_visible_decode"] == 4 * 32 * 6144
    assert out["positions_picked_prefill"] == 10_000_000
    assert out["expert_assignments_decode"] / out["decode_leaves"] == 96
    assert out["expert_load_max_over_mean"] == 1.5
    least = counts.decode_step_min_seconds(
        s, 31, 31 * 6144.0, PEAK, assignments=16.0, picked=31 * 2048.0)
    assert abs(out["decode_least_s"] - 4 * least["seconds"]) < 1e-12
    assert out["index_bytes_a_step"] == least["index_bytes"]
    assert out["least_bytes_a_step"] == least["bytes"]
    assert out["decode_device_s"] == 0.09 and out["prefill_device_s"] == 0.9
    pre = counts.prefill_min_seconds(s, 5888.0, PEAK)
    assert abs(out["prefill_least_s"] - 2 * pre["seconds"]) < 1e-12
    cell = common.Cell(CELL)
    read = {m["name"]: reducers.read_metric(m["name"], obs, cell.dir)
            for m in cell.per_layer() if m["name"].startswith("glm_")}
    assert all(v is not None for v in read.values()), read
    assert read["glm_picked_share_pct"] == pytest.approx(
        100 * 31 * 2048 / (32 * 6144))
    assert read["glm_kv_read_amplification"] == pytest.approx(32 / 31)
    assert 1.0 < read["glm_index_bytes_share_pct"] < 1.3
    assert 0 < read["glm_decode_roofline_pct"] < 100
    assert 0 < read["glm_prefill_roofline_pct"] < 100
    assert read["glm_prefill_ms_per_launch"] == pytest.approx(450.0)
    assert read["glm_expert_tokens_per_step"] == 96


# ----------------------------------------------------------------- the walk

def test_rehearsal_of_the_cell_is_correct_and_compiles_nothing_late():
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert r["correct"], rows
    assert r["failed"] == 0 and rows["compiled_in_window"]["value"] == 0
    assert rows["served_logit_gap"]["value"] < 0.01      # float32 on the CPU
    assert r["end_to_end"]["decode_tokens_per_s"] > 0


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", ["picks_left_the_most_recent",
                                   "head_weights_left_out"])
def test_correct_comes_out_false_for_a_planted_fault(monkeypatch, fault):
    import jax.numpy as jnp

    from pdnlp_tpu.models import latent_moe as lm

    real = lm.index_scores
    if fault == "picks_left_the_most_recent":
        # the mechanism left out, in the PROGRAM: scores that fall with age
        def broken(qI, w, kI):
            S = kI.shape[1]
            return jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32),
                                    (qI.shape[0], qI.shape[1], S))
    else:
        # I = sum_h relu(q . k): the heads' weights, of both signs, dropped
        def broken(qI, w, kI):
            return real(qI, jnp.ones_like(w), kI)

    monkeypatch.setattr(lm, "index_scores", broken)
    r = rehearse(CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert not r["correct"]
    assert not (rows["served_logit_gap"]["ok"]
                and rows["routing_swap_share"]["ok"]), rows


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_the_controls_fail_the_limits_and_bfloat16_lies_below_them(seed):
    """The reference computed in fp8, and the reference whose every query
    attends to the most recent ``index_topk`` positions (``sel-recent``: the
    mechanism left out), each in the program's place and judged by the
    configuration's OWN limits as the rehearsal reads them: both fail by
    the gap.  The same in bfloat16 — the precision the configuration states
    for the matmuls — lies below either.  (It lies FAR above float32 here:
    with 16 picks of 48-88 positions and a few of them carrying the softmax,
    a bfloat16 index score that swaps the 16th pick for the 17th moves a
    logit by 1 and more at the tiny size; at the published sizes a query
    picks 2 048, and the chip run is where each is held to the limits,
    PERF.md section 2.)"""
    from benchmark.kinds import closed_loop_latent_dsa as kind

    assert kind._precisions("sel-recent") == ("f32", "recent")
    assert kind._precisions("fp8") == ("fp8", "index")
    cfg = config()
    sizes = sizes_of(cfg, rehearse=True)
    limits = {**cfg["check"], **cfg["rehearse"]["check"]}
    rng = np.random.default_rng(seed)
    served = [(rng.integers(5, 1000, 48).tolist(),
               rng.integers(5, 1000, 40).tolist()) for _ in range(5)]
    rows = {}
    for name in ("bf16", "fp8", "sel-recent"):
        gaps, margins = kind.reference_gaps(served, seed, sizes, (3,),
                                            lowprec=name)
        checks = common.Checks()
        kind.judge(checks, [list(zip(gs, ms))
                            for gs, ms in zip(gaps, margins)], limits)
        rows[name] = {r["check"]: r for r in checks.rows}
    assert not rows["fp8"]["served_logit_gap"]["ok"], rows
    assert not rows["sel-recent"]["served_logit_gap"]["ok"], rows
    assert rows["bf16"]["served_tokens_compared"]["ok"], rows
    for name in ("fp8", "sel-recent"):
        assert rows["bf16"]["served_logit_gap"]["value"] \
            < rows[name]["served_logit_gap"]["value"], (name, rows)
