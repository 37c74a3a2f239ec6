"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.  Needs nothing but JAX's own reader.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>`` with, among others, the lines ``XLA Modules`` (one event
per launch of a compiled program, named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO operation, named by the instruction's whole
text, control-flow parents enclosing their bodies); and ``/host:CPU`` with one line per thread, on which
``jax.profiler.TraceAnnotation`` spans appear under their own names.

Definitions:
- busy: the union of the intervals on a chip's ``XLA Ops`` line (a parent and
  its children count once); averaged over the chips that ran anything;
- an operation's time: its SELF time — its duration less its children's — so
  a ``while`` does not count its body twice;
- a program's time: the sum of its ``XLA Modules`` events on chip 0;
- an idle gap: an interval inside the window in which chip 0 ran nothing,
  named by the host annotation that covers most of it, or else by the
  programs before and after it;
- exposed collective time: the part of collective operations' intervals
  during which no other operation's interval is open on that chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> dict:
    """-> {"devices": {plane: {"ops": [(name, start, end)], "modules": [...]}},
    "host": [(name, start, end)]} in nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            got = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    name = op_name(ev.name) if key == "ops" else ev.name
                    got[key].append((name, s, s + int(ev.duration_ns)))
            devices[plane.name] = got
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.12 = bf16[..] fusion(..), kind=..``): keep the instruction's
    name, and mark a custom call (a Pallas kernel) as such."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%").strip()
    if " custom-call(" in rest and not name.startswith("custom-call"):
        name = "custom-call:" + name
    return name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Per-name self time of properly nested events."""
    out: Dict[str, int] = {}
    stack: List[list] = []   # [name, end, child_ns, start]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end, child, st = stack.pop()
            out[n] = out.get(n, 0) + (end - st) - child
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([name, e, 0, s])
    while stack:
        n, end, child, st = stack.pop()
        out[n] = out.get(n, 0) + (end - st) - child
    return out


def program_name(event_name: str) -> str:
    """``jit__pdecode_fn(1234)`` -> ``jit__pdecode_fn``."""
    return event_name.split("(", 1)[0]


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def summarize(trace: dict, window_s: float, top: int = 10) -> dict:
    """The summary every trace reader takes its numbers from."""
    devs = [d for d in trace["devices"].values() if d["ops"]]
    if not devs:
        raise ValueError("the trace holds no device operation")
    first = devs[0]
    busy = [union([(s, e) for _, s, e in d["ops"]]) for d in devs]
    busy_s = sum(total(b) for b in busy) / len(busy) / 1e9
    ops = self_times(first["ops"])
    programs: Dict[str, dict] = {}
    for name, s, e in first["modules"]:
        p = programs.setdefault(program_name(name), {"seconds": 0.0, "launches": 0})
        p["seconds"] += (e - s) / 1e9
        p["launches"] += 1
    coll = union([(s, e) for n, s, e in first["ops"] if COLLECTIVE.match(n)])
    other = union([(s, e) for n, s, e in first["ops"]
                   if not COLLECTIVE.match(n) and not n.startswith("while")])
    exposed = total(_subtract(coll, other)) / 1e9
    return {
        "chips": len(devs), "window_s": window_s, "busy_s": busy_s,
        "op_self_s": {k: v / 1e9 for k, v in ops.items()},
        "programs": programs,
        "collective_s": total(coll) / 1e9, "collective_exposed_s": exposed,
        "device_ops": [[n, v / 1e9] for n, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps(first, trace["host"], top),
    }


def idle_gaps(dev: dict, host: List[Tuple[str, int, int]], top: int = 10):
    """Idle time of one chip grouped by what the host was doing (the
    ``bench:`` annotation overlapping most of the gap), else by the programs
    around it; the ``top`` largest groups as [name, seconds]."""
    busy = union([(s, e) for _, s, e in dev["ops"]])
    mods = sorted(dev["modules"], key=lambda m: m[1])
    mod_starts = [m[1] for m in mods]
    mod_ends = sorted(m[2] for m in mods)
    ends_names = [program_name(n) for n, _, _ in sorted(mods, key=lambda m: m[2])]
    host_starts = [h[1] for h in host]
    groups: Dict[str, int] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        best, best_ov = None, 0
        # annotations that began before the gap's end, latest first; spans of
        # one thread do not nest deeply, so a few steps back is enough
        i = bisect.bisect_left(host_starts, s1)
        for name, hs, he in reversed(host[max(0, i - 8):i]):
            ov = min(he, s1) - max(hs, e0)
            if ov > best_ov:
                best, best_ov = name, ov
        if best is not None and best_ov * 2 >= gap:
            label = "host:" + best[len("bench:"):]
        else:
            slack = min(1000, gap // 2)   # a module's edges and its ops' differ
            b = bisect.bisect_right(mod_ends, e0 + slack)
            a = bisect.bisect_left(mod_starts, s1 - slack)
            label = (f"after_{ends_names[b - 1] if b else 'window_start'}"
                     f"__before_"
                     f"{program_name(mods[a][0]) if a < len(mods) else 'window_end'}")
        groups[label] = groups.get(label, 0) + gap
    return [[n, v / 1e9] for n, v in
            sorted(groups.items(), key=lambda kv: -kv[1])[:top]]
