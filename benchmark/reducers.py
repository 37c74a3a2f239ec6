"""The fixed set of reducers a metric file (``metrics/<name>.json``) may
name, and the reader that finds a metric by its name.

A metric file holds ``layer``, ``unit``, ``better``, ``moves``, ``source``
(the same values ``BENCHMARK.json`` lists), a ``reducer`` from this module
and its ``args``.  A metric that needs code of its own is a
``metrics/<name>.py`` with ``read(obs) -> float | None`` beside its json.
A reader that finds nothing to read returns ``None`` and the metric is left
out of the line.

``obs`` is what a traffic kind observed: ``counters`` (numbers the program
or the benchmark counted over the window), ``samples`` (lists), ``trace``
(``trace/xplane.summarize``'s dict, or None), ``sizes`` and ``peaks``.
"""
from __future__ import annotations

import importlib.util
import os
import re
from typing import Optional

from benchmark import common, counts


def _c(obs, key):
    if isinstance(key, (int, float)):
        return float(key)
    if isinstance(key, list):
        vals = [_c(obs, k) for k in key]
        return None if any(v is None for v in vals) else sum(vals)
    v = obs["counters"].get(key)
    return None if v is None else float(v)


def counter(obs, key, scale=1.0):
    v = _c(obs, key)
    return None if v is None else v * scale


def ratio(obs, num, den, scale=100.0):
    """``scale * num / den`` over counters (a list of keys is summed)."""
    n, d = _c(obs, num), _c(obs, den)
    if n is None or not d:
        return None
    return scale * n / d


def sample_percentile(obs, key, p):
    xs = obs["samples"].get(key)
    return common.percentile(xs, p) if xs else None


def _programs(obs, pattern):
    t = obs.get("trace")
    if not t:
        return None
    rx = re.compile(pattern)
    hits = [v for k, v in t["programs"].items() if rx.search(k)]
    return hits or None


def trace_program_ms(obs, program, per="launch", steps_key=None):
    """Device time of the programs matching ``program``, in ms per launch —
    or per step, dividing by ``steps_key``'s share of the traced window."""
    hits = _programs(obs, program)
    if not hits:
        return None
    secs = sum(h["seconds"] for h in hits)
    n = sum(h["launches"] for h in hits)
    if per == "step":
        n = _c(obs, steps_key)
    return 1e3 * secs / n if n else None


def trace_program_share_pct(obs, program):
    """Share of the chip's busy time spent in the matching programs."""
    t = obs.get("trace")
    hits = _programs(obs, program)
    if not t or not t["busy_s"]:
        return None
    return 100.0 * sum(h["seconds"] for h in hits or []) / t["busy_s"]


def trace_op_share_pct(obs, op):
    """Share of busy time in operations whose name matches ``op``."""
    t = obs.get("trace")
    if not t or not t["busy_s"]:
        return None
    rx = re.compile(op)
    total = sum(t["op_self_s"].values())
    if not total:
        return None
    return 100.0 * sum(v for k, v in t["op_self_s"].items()
                       if rx.search(k)) / total


def trace_idle_pct(obs):
    t = obs.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def trace_collective_exposed_pct(obs):
    """Collective time with no compute beside it, as a share of busy time."""
    t = obs.get("trace")
    if not t or not t["busy_s"] or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["busy_s"]


def slot_mfu_pct(obs):
    """Model FLOPs of the SLOT tokens trained (padding included: the program
    chose the padded layout and the chip computes it) per second, over chips
    times the bf16 peak.  From the host clock's window."""
    c, peaks = obs["counters"], obs["peaks"]
    if not peaks:
        return None
    return 100.0 * c["step_flops"] * c["steps"] / c["window_s"] / (
        c["chips"] * peaks["bf16_flops"])


def hbm_peak_pct(obs):
    c, peaks = obs["counters"], obs["peaks"]
    if not peaks or not c.get("memory_peak_bytes"):
        return None
    return 100.0 * c["memory_peak_bytes"] / peaks["hbm_bytes"]


def decode_roofline_pct(obs, program):
    """Least time of a decode step (``counts.decode_step_min_seconds`` at
    the window's mean live rows and live K/V) over the step's device time."""
    ms = trace_program_ms(obs, program)
    c = obs["counters"]
    if ms is None or not obs["peaks"] or not c.get("decode_steps"):
        return None
    least = counts.decode_step_min_seconds(
        obs["sizes"], rows=c["live_rows_sum"] / c["decode_steps"],
        live_tokens=c["live_kv_tokens_sum"] / c["decode_steps"],
        peak=obs["peaks"])
    obs.setdefault("notes", {})[program + "_bound"] = least["bound"]
    return 100.0 * least["seconds"] * 1e3 / ms


REDUCERS = {f.__name__: f for f in (
    counter, ratio, sample_percentile, trace_program_ms,
    trace_program_share_pct, trace_op_share_pct, trace_idle_pct,
    trace_collective_exposed_pct, slot_mfu_pct, hbm_peak_pct,
    decode_roofline_pct)}


def read_metric(name: str, obs: dict, bench_dir: str = common.HERE
                ) -> Optional[float]:
    """The value of per-layer metric ``name`` from ``obs``, or None."""
    code = os.path.join(bench_dir, "metrics", name + ".py")
    if os.path.exists(code):
        spec = importlib.util.spec_from_file_location("bench_metric_" + name, code)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(obs)
    else:
        spec = common.load_json(bench_dir, "metrics", name + ".json")
        v = REDUCERS[spec["reducer"]](obs, **spec.get("args", {}))
    return None if v is None else float(v)
