"""The latent-attention, sparse-expert configuration and the cell this PR
adds, on the CPU: counts from shapes, the cell found as data, a
``--rehearse`` walk, ``correct`` coming out false for planted faults, and
the control failing a limit."""
import argparse

import numpy as np
import pytest

from benchmark import common, counts_axk1
from benchmark.run import run_cell

CONFIG = "ax-k1-ep16-share"
LATENT_CELL = "axk1-decode-longdoc-saturated"
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "hbm_bytes": 16e9}


def config():
    return common.load_json(common.HERE, "configs", CONFIG + ".json")


def sizes_of(cfg):
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    sizes["rope_scaling"] = cfg["rope_scaling"]
    return sizes


def rehearse(workload, seed=5, seconds=2.0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=0, rehearse=True)
    assert run_cell(ns) == common.REHEARSAL_EXIT
    return ns.result


# ------------------------------------------------------ counts from shapes

def test_parameters_and_cache_bytes_from_the_published_widths():
    s = sizes_of(config())
    p = counts_axk1.parts(s)
    assert round(p["attention"] / 1e6, 1) == 101.1
    assert p["dense_ffn"] == 3 * 7168 * 18432
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048
    assert p["router"] == 7168 * 192
    # 497.5 + 5 x (146.6 + 12 x 44.04) + 2 x 146.8 M (ISSUE 27)
    assert abs(counts_axk1.params_held(s) / 1e9 - 4.166) < 0.001
    assert counts_axk1.cache_bytes_per_token(s) == 6 * 576 * 2 == 6912
    assert counts_axk1.expected_assignments(s, 128) == 64.0


def test_a_decode_step_is_bound_by_bytes_and_a_prompt_by_flops():
    s = sizes_of(config())
    step = counts_axk1.decode_step_min_seconds(s, 128, 128 * 2300, PEAK)
    weights = (counts_axk1.params_held(s) - 20480 * 7168 + 128 * 7168) * 2
    assert step["bound"] == "bytes"
    assert step["bytes"] == weights + (128 * 2300 + 128) * 6912
    assert 0.0115 < step["seconds"] < 0.0130          # 9.8 + 2.5 ms
    # the absorbed attention: 64 x (576 + 512) x 2 a cached position a layer
    more = counts_axk1.decode_step_min_seconds(s, 128, 128 * 2300 + 1, PEAK)
    assert more["flops"] - step["flops"] == 6 * 64 * (576 + 512) * 2
    pre = counts_axk1.prefill_min_seconds(s, 2048, PEAK)
    assert pre["bound"] == "flops" and 0.025 < pre["seconds"] < 0.040
    # twice the prompt: the matrices' part doubles, the attention's
    # quadruples, the head's stays
    twice = counts_axk1.prefill_min_seconds(s, 4096, PEAK)
    attn = 0.5 * 2048 * 2048 * 6 * 64 * 320 * 2.0
    head = 2.0 * 7168 * 20480
    assert abs(twice["flops"] - (2 * (pre["flops"] - attn - head) + 4 * attn
                                 + head)) < 1e-6 * twice["flops"]


# ------------------------------------------------------- the cells as data

def test_the_configuration_file_holds_the_published_numbers():
    cfg = config()
    published = {"hidden_size": 7168, "intermediate_size": 18432,
                 "moe_intermediate_size": 2048, "num_attention_heads": 64,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_experts_per_tok": 8, "n_group": 8,
                 "topk_group": 4, "n_shared_experts": 1,
                 "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
                 "max_position_embeddings": 131072, "rope_theta": 10000}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"]["factor"] == 32
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == ["n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"]) == (6, 12, 20480, 192)
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192, "vocab_size": 163840}
    for key in ("source", "deployment", "precision", "assumed", "check",
                "rehearse"):
        assert cfg[key], key
    a = cfg["assumed"]
    assert a["slots"] * a["max_len"] == a["pool_pages"] * a["page_size"]


def test_the_new_cell_is_found_and_reports_what_it_says():
    cell = common.Cell(LATENT_CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop_latent_moe"
    assert [m["name"] for m in cell.end_to_end()] == ["decode_tokens_per_s",
                                                      "setup_s"]
    # what the saturated BERT cell's ``.json`` readers can read is read by
    # them (its four ``.py`` readers are held to one cell each); the cell's
    # own metrics are those four again and what only this family has
    other = {m["name"] for m in common.Cell("decode-file-saturated-mixedout").per_layer()}
    names = {m["name"] for m in cell.per_layer()}
    assert len(names & other) == 7 and len(names - other) == 10
    assert not any("roofline" in n for n in names & other)
    tr = cell.traffic
    assert tr["clients"] == cell.config["assumed"]["slots"] == tr["cycle"] == 128
    assert (tr["prompt_tokens"]["hi"] + tr["new_tokens"]
            <= cell.config["assumed"]["max_len"])


def test_a_program_without_the_new_leaves_leaves_the_metrics_out():
    """The parent commit records no expert or cache attributes: the numbers
    the kind computes are left out and the readers return None."""
    from benchmark import reducers
    from benchmark.kinds import closed_loop_latent_moe as kind

    obs = {"counters": {"decode_steps": 0}, "trace": None, "peaks": None,
           "sizes": sizes_of(config())}
    obs["counters"].update(kind.layer_numbers(obs, [], None))
    cell = common.Cell(LATENT_CELL)
    shared = {m["name"]
              for m in common.Cell("decode-file-saturated-mixedout").per_layer()}
    for m in cell.per_layer():
        if m["name"] not in shared and m["source"] != "device_trace":
            assert reducers.read_metric(m["name"], obs, cell.dir) is None


def test_the_kind_reads_its_numbers_from_leaves_and_programs():
    from benchmark.kinds import closed_loop_latent_moe as kind

    def rec(name, t0, dur, **attrs):
        return {"name": name, "t0": t0, "dur": dur, "attrs": attrs}

    recs = [rec("admit", 0.0, 0.001, seated=2, waiting=0)]
    for i in range(4):
        t = i * 0.05
        recs += [rec("decode.dispatch", t, 0.002, kv_positions_read=1000,
                     kv_positions_live=400),
                 rec("decode.device_wait", t + 0.002, 0.02),
                 rec("decode.fetch", t + 0.022, 0.003, expert_assignments=300),
                 rec("decode.emit", t + 0.025, 0.015)]
    s = sizes_of(config())
    obs = {"counters": {"decode_steps": 40, "live_rows_sum": 40 * 128,
                        "live_kv_tokens_sum": 40 * 128 * 2300.0, "bursts": 40,
                        "prefills": 10, "prefill_tokens": 20480},
           "trace": {"programs": {
               "jit__pdecode_fn(1)": {"seconds": 0.08, "launches": 4},
               "jit__prefill_fn(2)": {"seconds": 0.12, "launches": 2}}},
           "peaks": PEAK, "sizes": s}
    out = kind.layer_numbers(obs, recs, np.array([10, 30, 20, 20] * 3))
    assert out["kv_positions_read"] / out["kv_positions_live"] == 2.5
    assert out["expert_assignments_decode"] / out["decode_leaves"] == 300
    assert out["expert_load_max_over_mean"] == 1.5
    assert abs(out["emit_ms_a_step"] - 15.0) < 1e-9
    assert abs(out["fetch_ms_a_step"] - 3.0) < 1e-9
    assert abs(out["admit_ms_a_seat"] - 0.5) < 1e-9
    assert abs(out["host_exposed_ms_a_step"] - (0.19 - 0.08) / 4 * 1e3) < 1e-6
    least = counts_axk1.decode_step_min_seconds(s, 128, 128 * 2300.0, PEAK,
                                                assignments=60.0)
    assert abs(out["decode_least_s"] - 4 * least["seconds"]) < 1e-12
    assert out["decode_device_s"] == 0.08 and out["prefill_device_s"] == 0.12
    assert 0 < out["prefill_least_s"] / out["prefill_device_s"] < 1.05


# ----------------------------------------------------------------- the walk

def test_rehearsal_of_the_latent_cell_is_correct_and_compiles_nothing_late():
    r = rehearse(LATENT_CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert r["correct"], rows
    assert r["failed"] == 0 and rows["compiled_in_window"]["value"] == 0
    assert rows["served_logit_gap"]["value"] < 0.01      # float32 on the CPU
    assert r["end_to_end"]["decode_tokens_per_s"] > 0


def test_a_cycle_is_levelled_and_still_the_generators_multiset():
    """Every ``cycle`` requests hold the multiset the generator that is there
    gives; every ``strata`` consecutive ones hold one length of each part of
    it, so a window that holds under one cycle still holds the mix."""
    import itertools

    from benchmark import loadgen
    from benchmark.kinds import closed_loop_latent_moe as kind

    tr = common.load_json(common.HERE, "traffic", "decode-longdoc-saturated.json")
    cycle, strata = tr["cycle"], tr["strata"]
    assert (cycle, strata) == (128, 8)

    def lengths(stream, n):
        return [len(p) for p, _ in itertools.islice(stream, n)]

    plain = sorted(lengths(loadgen.closed_loop_prompts(tr, 3, 1000), cycle))
    got = lengths(kind.levelled_prompts(tr, 3, 1000), 3 * cycle)
    other = lengths(kind.levelled_prompts(tr, 4, 1000), cycle)
    for c in range(3):
        assert sorted(got[c * cycle:(c + 1) * cycle]) == plain
    assert got[:cycle] != got[cycle:2 * cycle] and got[:cycle] != other
    per = cycle // strata
    part = {n: i // per for i, n in enumerate(plain)}    # lengths are distinct
    assert len(part) == cycle
    for b in range(0, 3 * cycle, strata):
        assert sorted(part[n] for n in got[b:b + strata]) == list(range(strata))
    # any 93 consecutive requests: the long bucket's share within two prompts
    for a in range(0, 2 * cycle, 7):
        assert abs(sum(n > 2048 for n in got[a:a + 93]) - 46.5) <= 2.5


def test_round_ms_tells_plain_rounds_from_those_that_held_a_prefill():
    from benchmark.kinds import closed_loop_latent_moe as kind

    gaps = ([0.054] * 3 + [0.160]) * 20 + [0.055] * 10
    out = kind.round_ms(gaps)
    assert out["rounds"] == 90 and out["with_prefill"] == 20
    assert out["decode_p50"] == pytest.approx(54.0)
    assert out["with_prefill_mean"] == pytest.approx(160.0)
    assert kind.round_ms([]) == {}


def test_one_requests_swaps_are_printed_beside_the_share_of_all(capfd):
    """A fault of one slot raises one request's share of swaps long before
    it raises the share of all: printed (no limit yet), judged by the share
    of all positions."""
    from benchmark.kinds import closed_loop_latent_moe as kind

    limits = dict(config()["check"])
    sound = [(0.01, 0.5)] * 90 + [(0.5, 0.001)] + [(0.01, 0.001)] * 9
    hit = [(0.01, 0.5)] * 60 + [(0.5, 0.001)] * 40
    checks = common.Checks()
    kind.judge(checks, [sound] * 31 + [hit], limits)
    rows = {r["check"]: r for r in checks.rows}
    assert rows["routing_swap_share"]["value"] == (31 + 40) / 3200
    assert rows["routing_swap_share"]["ok"] and rows["served_logit_gap"]["ok"]
    assert '"worst_request_swap_share": 0.4' in capfd.readouterr().out


# ------------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", ["held_expert_zeroed", "k_rope_term_left_out"])
def test_correct_comes_out_false_for_a_planted_fault(monkeypatch, fault):
    from pdnlp_tpu.models import latent_moe

    import jax.numpy as jnp

    if fault == "held_expert_zeroed":
        real = latent_moe.held_experts

        def broken(f, idx, gates, *rest):     # held expert 1 adds nothing
            return real(f, idx, jnp.where(idx == 1, 0.0, gates), *rest)

        monkeypatch.setattr(latent_moe, "held_experts", broken)
    else:
        real = latent_moe.attend_absorbed
        monkeypatch.setattr(
            latent_moe, "attend_absorbed",
            lambda qn, qr, *rest: real(qn, jnp.zeros_like(qr), *rest))
    r = rehearse(LATENT_CELL)
    rows = {c["check"]: c for c in r["checks"]}
    assert not r["correct"]
    assert not (rows["served_logit_gap"]["ok"]
                and rows["routing_swap_share"]["ok"]), rows


@pytest.mark.parametrize("seed", [3, 11, 2 ** 31 + 5])
def test_the_fp8_control_fails_a_limit_and_bfloat16_does_not(seed):
    """The reference computed in fp8 in the program's place, judged by the
    configuration's OWN limits at the tiny size; the same in bfloat16 — the
    precision the configuration states — passes them."""
    from benchmark.kinds import closed_loop_latent_moe as kind

    cfg = config()
    sizes = sizes_of(cfg)
    sizes.update(cfg["rehearse"]["sizes"])
    limits = dict(cfg["check"])
    rng = np.random.default_rng(seed)
    served = [(rng.integers(5, 1000, 48).tolist(),
               rng.integers(5, 1000, 40).tolist()) for _ in range(5)]
    verdict = {}
    for prec in ("bf16", "fp8"):
        gaps, margins = kind.reference_gaps(served, seed, sizes, (3,),
                                            lowprec=prec)
        checks = common.Checks()
        kind.judge(checks, [list(zip(gs, ms))
                            for gs, ms in zip(gaps, margins)], limits)
        verdict[prec] = {r["check"]: r["ok"] for r in checks.rows}
    assert all(verdict["bf16"].values()), verdict
    assert not all(verdict["fp8"].values()), verdict
