"""The program's leaf spans as the benchmark reads them: the helper and
every metric file over it on made-up records, and the trace reducer's
idle-gap labels on made-up intervals with the leaves' own names."""
import os

import pytest

from benchmark import common, reducers, spans
from benchmark.trace import xplane

MS = 1e-3


def rec(name, t0_ms, dur_ms, **attrs):
    return {"name": name, "t0": t0_ms * MS, "dur": dur_ms * MS, "tid": 1,
            "depth": 0, "attrs": dict(attrs, replica=0)}


def request(rid, submit, seated, first, done):
    return {"name": "request", "t0": submit * MS, "dur": (done - submit) * MS,
            "tid": 1, "depth": 0,
            "attrs": {"rid": rid, "t_submit": submit * MS,
                      "t_seated": seated * MS, "t_first_token": first * MS,
                      "t_done": done * MS, "tokens_out": 3,
                      "prefix_hit": "partial"}}


def two_rounds():
    """Two decode steps, one of them after a seating and a chunk prefill:
    wall 0..300 ms; device waits 100 + 20 + 100; emits 8 + 2 + 6; fetches
    2 + 1 + 2; one admit of 0.6 ms that seated 2."""
    return [
        rec("decode.dispatch", 0, 10, round=1, phase="decode"),
        rec("decode.device_wait", 10, 100, round=1),
        rec("decode.fetch", 110, 2, round=1, bytes=10),
        rec("decode.emit", 112, 8, round=1, rows=3),
        rec("admit", 120, 0.6, round=2, seated=2, waiting=0),
        rec("chunk.dispatch", 121, 9, round=2, phase="prefill"),
        rec("chunk.device_wait", 130, 20, round=2),
        rec("chunk.fetch", 150, 1, round=2, bytes=5),
        rec("chunk.emit", 151, 2, round=2, rows=2),
        rec("decode.dispatch", 160, 32, round=2, phase="decode"),
        rec("decode.device_wait", 192, 100, round=2),
        rec("decode.fetch", 292, 2, round=2, bytes=10),
        rec("decode.emit", 294, 6, round=2, rows=5),
        # a: wholly inside the session; b: first token before it began
        # (left out); c: no first token at all (left out)
        request("a", 50, 120.5, 153, 299),
        request("b", -400, -300, -10, 298),
        {"name": "request", "t0": 0.0, "dur": 0.1, "tid": 1, "depth": 0,
         "attrs": {"rid": "c", "t_submit": 0.0, "t_seated": None,
                   "t_first_token": None, "t_done": 0.1}},
        # what --trace adds beside the leaves is not a leaf
        {"name": "hop", "t0": 0.5, "dur": 0.0, "tid": 0, "depth": 0,
         "attrs": {"hop": "decode", "request_id": "a"}},
    ]


WANT = {
    "host_exposed_ms_per_step": (300 - 220) / 2,
    "emit_ms_per_step": 16 / 2,
    "logits_fetch_ms_per_step": 5 / 2,
    "admit_ms_per_seat": 0.6 / 2,
    "queue_wait_p90_ms": 70.5,
    "seat_to_first_token_p90_ms": 32.5,
}


def new_metrics():
    b = common.load_json(common.ROOT, "BENCHMARK.json")
    return [m for m in b["per_layer"]
            if os.path.exists(os.path.join(common.HERE, "metrics",
                                           m["name"] + ".py"))]


def test_the_span_metrics_are_the_ten_the_benchmark_lists():
    names = sorted(m["name"] for m in new_metrics())
    assert len(names) == 10
    assert sum(n.startswith("steady_") for n in names) == 6
    assert sum(n.startswith("sat_") for n in names) == 4
    for m in new_metrics():
        assert m["source"] == "program_counter" and m["unit"] == "ms"
        assert len(m["workloads"]) == 1


@pytest.mark.parametrize("name", [m["name"] for m in new_metrics()])
def test_metric_file_on_made_up_records(name):
    want = WANT[name.split("_", 1)[1]]
    obs = {"counters": {}, "samples": {}, "trace": None, "spans": two_rounds()}
    assert reducers.read_metric(name, obs) == pytest.approx(want)
    # no record (a program that has no such span): nothing, and no raise
    assert reducers.read_metric(name, dict(obs, spans=[])) is None
    # only the --trace stream, no leaf: nothing either
    assert reducers.read_metric(name, dict(obs, spans=two_rounds()[-1:])) is None


def test_the_helper_reads_the_process_global_tracer_and_survives_its_absence():
    assert isinstance(spans.records({"counters": {}}), list)
    assert spans.steps([]) == 0 and spans.session([]) is None
    assert spans.per_step_ms([rec("decode.emit", 0, 1)], "emit") is None


def test_a_request_whose_first_token_fell_outside_the_session_is_left_out():
    recs = two_rounds()
    assert spans.request_wait_ms(recs, "t_submit", "t_seated", 90) == 70.5
    late = recs + [request("d", 0, 10, 301, 400)]      # first token after
    assert spans.request_wait_ms(late, "t_submit", "t_seated", 90) == 70.5
    inside = recs + [request("e", 100, 280, 290, 299)]
    assert spans.request_wait_ms(inside, "t_submit", "t_seated", 90) == pytest.approx(180.0)
    assert spans.request_wait_ms(inside, "t_submit", "t_seated", 50) == 70.5


# ------------------------------------------------------ idle gaps by leaf

def ns(ms):
    return int(ms * 1e6)


def host(name, t0_ms, t1_ms):
    return ("bench:" + name, ns(t0_ms), ns(t1_ms))


def test_idle_gaps_are_named_by_the_leaf_that_covers_them():
    """A decode step ends at 100 ms, the next program starts at 112: the
    worker noticed (device_wait ends), fetched 2 ms, emitted 8.5 ms."""
    dev = {"ops": [("fusion.1", ns(0), ns(100)), ("fusion.2", ns(112), ns(200))],
           "modules": [("jit__pdecode_fn(1)", ns(0), ns(100)),
                       ("jit__pchunk_fn(2)", ns(112), ns(200))]}
    leaves = sorted([
        host("decode.dispatch", -5, 0.2), host("decode.device_wait", 0.2, 100.3),
        host("decode.fetch", 100.3, 102.3), host("decode.emit", 102.3, 110.8),
        host("admit", 110.8, 111.0), host("chunk.dispatch", 111.0, 112.5),
        host("submit", 105.0, 105.1),        # the load thread's, beside them
    ], key=lambda e: e[1])
    gaps = dict(xplane.idle_gaps(dev, leaves))
    assert list(gaps) == ["host:decode.emit"]
    assert gaps["host:decode.emit"] == pytest.approx(0.012)


def test_idle_gaps_fall_back_to_programs_when_short_leaves_share_a_gap():
    """After a chunk program: fetch 1 ms, emit 1.2 ms, the next dispatch
    1.3 ms — no ONE leaf covers half of the 3.5 ms gap."""
    dev = {"ops": [("fusion.1", ns(0), ns(50)), ("fusion.2", ns(53.5), ns(150))],
           "modules": [("jit__pchunk_fn(2)", ns(0), ns(50)),
                       ("jit__pdecode_fn(1)", ns(53.5), ns(150))]}
    leaves = [host("chunk.device_wait", 0.1, 50.0), host("chunk.fetch", 50.0, 51.0),
              host("chunk.emit", 51.0, 52.2), host("decode.dispatch", 52.2, 53.6),
              host("decode.device_wait", 53.6, 150.1)]
    gaps = dict(xplane.idle_gaps(dev, leaves))
    assert list(gaps) == ["after_jit__pchunk_fn__before_jit__pdecode_fn"]
    assert gaps["after_jit__pchunk_fn__before_jit__pdecode_fn"] == \
        pytest.approx(0.0035)


def test_a_recorded_closed_loop_window_by_thirds():
    """``kinds/serve.thirds`` on the marks of a recorded run (seconds, tokens
    seen, requests sent, prefill launches at the window's four edges): the
    first third climbs — clusters still merging — and the last two agree."""
    from benchmark.kinds import serve

    marks = [(100.0, 0, 256, 400), (110.004, 112_045, 1131, 900),
             (120.001, 232_009, 2068, 1400), (130.003, 352_033, 3006, 1900)]
    got = serve.thirds(marks)
    assert [round(t["tokens_per_s"]) for t in got] == [11200, 12000, 12000]
    assert [t["rows_per_prefill"] for t in got] == [1.75, 1.874, 1.876]
    # a third without a prefill launch has no rows a launch, not a zero
    assert serve.thirds([(0.0, 0, 4, 7), (1.0, 50, 4, 7)]) == [
        {"tokens_per_s": 50.0, "rows_per_prefill": None}]
    assert serve.thirds([]) == [] and serve.thirds(marks[:1]) == []


def test_decode_steps_by_the_rows_launched():
    from benchmark.kinds import serve

    recs = [rec("decode.dispatch", 0, 1, rows=16), rec("decode.dispatch", 2, 1, rows=16),
            rec("decode.dispatch", 4, 1, rows=256), rec("decode.emit", 5, 1, rows=3),
            rec("decode.dispatch", 6, 1)]
    assert serve.steps_by_rows({"spans": recs}) == {"16": 2, "256": 1}
    assert serve.steps_by_rows({"spans": []}) == {}
