"""The reduction from a profiler trace to numbers: on a small trace recorded
on a ``TPU v5 lite`` (``trace/record_fixture.py``: two named programs, three
launches each, host annotations between them), and on hand-made intervals."""
import os

import pytest

from benchmark import common, reducers
from benchmark.trace import xplane

FIXTURE = os.path.join(common.HERE, "tests", "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(xplane.load(FIXTURE), window_s=0.065079327)


def test_recorded_trace_programs_and_busy_time(summary):
    assert summary["chips"] == 1
    big, small = summary["programs"]["jit_fixture_big"], summary["programs"]["jit_fixture_small"]
    assert big["launches"] == small["launches"] == 3
    assert big["seconds"] == pytest.approx(0.000309736, abs=1e-9)
    assert small["seconds"] == pytest.approx(2.2575e-05, abs=1e-9)
    assert summary["busy_s"] == pytest.approx(0.000332266, abs=1e-9)
    # the programs' time is the chip's busy time, to within the launch edges
    assert summary["busy_s"] == pytest.approx(big["seconds"] + small["seconds"], rel=0.01)


def test_recorded_trace_operations_have_short_names_and_self_times(summary):
    ops = summary["op_self_s"]
    assert ops["convolution_multiply_fusion.2"] == pytest.approx(0.000277438, abs=1e-9)
    # the loop's own time excludes its body: 24 matmuls ran inside it
    assert ops["while"] < 1e-6
    assert all(" " not in n and "%" not in n for n in ops)
    assert summary["device_ops"][0][0] == "convolution_multiply_fusion.2"
    assert sum(ops.values()) == pytest.approx(summary["busy_s"], rel=1e-6)


def test_recorded_trace_gaps_are_named_by_the_host_annotation(summary):
    names = [n for n, _ in summary["idle_gaps"]]
    assert "host:data_wait" in names          # the 5 ms sleeps, chip idle
    gaps = dict(summary["idle_gaps"])
    assert 0.012 < gaps["host:data_wait"] < 0.03
    assert summary["collective_s"] == 0.0


def test_reducers_read_the_recorded_trace(summary):
    obs = {"counters": {"steps": 6}, "samples": {}, "trace": summary,
           "peaks": common.peaks_for("TPU v5 lite"), "sizes": {}}
    assert reducers.trace_program_ms(obs, "fixture_big") == pytest.approx(0.103245, rel=1e-4)
    assert reducers.trace_program_ms(obs, "fixture_", per="step", steps_key="steps") \
        == pytest.approx(1e3 * 0.000332311 / 6, rel=1e-4)
    assert reducers.trace_program_share_pct(obs, "fixture_small") == pytest.approx(6.794, rel=1e-3)
    assert reducers.trace_idle_pct(obs) == pytest.approx(99.489, rel=1e-4)
    assert reducers.trace_op_share_pct(obs, "^custom-call") == 0.0
    assert reducers.trace_collective_exposed_pct(obs) is None


def test_op_names():
    text = ('%fusion.12 = bf16[64,128]{1,0:T(8,128)(2,1)} fusion(bf16[64] %p), '
            'kind=kLoop, calls=%fused_computation')
    assert xplane.op_name(text) == "fusion.12"
    kernel = ('%fused_ce_fwd = f32[64]{0} custom-call(bf16[64,768] %x), '
              'custom_call_target="tpu_custom_call"')
    assert xplane.op_name(kernel) == "custom-call:fused_ce_fwd"
    assert xplane.op_name("%custom-call.3 = f32[] custom-call()") == "custom-call.3"
    assert xplane.program_name("jit__pdecode_fn(123456)") == "jit__pdecode_fn"


def test_union_and_self_times():
    assert xplane.union([(0, 10), (5, 12), (20, 30)]) == [(0, 12), (20, 30)]
    events = [("while", 0, 100), ("a", 10, 40), ("b", 50, 90), ("c", 200, 250)]
    assert xplane.self_times(events) == {"while": 30, "a": 30, "b": 40, "c": 50}


def test_exposed_collective_time_on_made_up_intervals():
    ops = [("fusion.1", 0, 100), ("all-reduce.1", 50, 150), ("fusion.2", 120, 130),
           ("all-reduce.2", 300, 340)]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": [("jit_step(1)", 0, 340)]}},
             "host": []}
    s = xplane.summarize(trace, window_s=1e-6)
    assert s["collective_s"] == pytest.approx(140e-9)
    # hidden: 50-100 under fusion.1 and 120-130 under fusion.2
    assert s["collective_exposed_s"] == pytest.approx(80e-9)
    assert s["busy_s"] == pytest.approx(190e-9)


def test_gaps_without_annotation_are_named_by_the_programs_around_them():
    ops = [("a", 0, 10), ("b", 1000, 1010)]
    mods = [("jit_first(1)", 0, 10), ("jit_second(2)", 1000, 1010)]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": []}
    s = xplane.summarize(trace, window_s=1e-5)
    assert s["idle_gaps"] == [["after_jit_first__before_jit_second", pytest.approx(990e-9)]]
    trace["host"] = [("bench:submit", 5, 900)]
    s = xplane.summarize(trace, window_s=1e-5)
    assert s["idle_gaps"][0][0] == "host:submit"
