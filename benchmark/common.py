"""What every cell shares: finding the cell's files by the names in
``BENCHMARK.json``, the look for a chip, the compile cache, the clock the
set-up time is read from, and the result line.

No cell's, configuration's, traffic mix's or metric's name appears in code:
each is a file found by name (``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json`` or ``.py``), and a
traffic file's ``kind`` names the module under ``kinds/`` that drives it.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_EXIT = 3


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding="utf-8") as f:
        return json.load(f)


def die(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def process_age_s() -> float:
    """Seconds since this process started (``/proc`` start time), so that
    ``setup_s`` counts the interpreter's and the imports' time too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(root, "BENCHMARK.json")
        self.dir = os.path.join(root, self.bench["paths"][0])
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            die(f"no workload {name!r} in BENCHMARK.json "
                f"(known: {', '.join(sorted(cells))})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(root, cfgs[self.entry["config"]]["file"])
        self.traffic = load_json(self.dir, "traffic",
                                 self.entry["traffic"] + ".json")

    def rehearsal(self, part: str) -> dict:
        """The configuration's tiny-size overrides for ``part`` of a run."""
        return self.config.get("rehearse", {}).get(part, {})

    def sizes(self, rehearse: bool) -> dict:
        """The configuration's numbers (the published sizes among them)."""
        sizes = {k: v for k, v in self.config.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        if rehearse:
            sizes.update(self.rehearsal("sizes"))
        return sizes

    def _listed(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._listed(m)]

    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._listed(m) and m["moves"] in mine]


def peaks_for(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["by_device_kind"]
    if kind not in table:
        die(f"device kind {kind!r} is not in benchmark/peaks.json; a device "
            "without published peaks is an error, not a default")
    return table[kind]


def find_devices(chips: int, rehearse: bool):
    """The chips this cell runs on, or exit: no accelerator, too few chips or
    an unknown kind ends the run non-zero with no result line."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if rehearse:
        if len(devs) < chips:
            die(f"the rehearsal needs {chips} (virtual) devices, JAX reports "
                f"{len(devs)}: set XLA_FLAGS=--xla_force_host_platform_"
                f"device_count={chips}")
        return devs, None
    if d.platform != "tpu":
        die(f"JAX found no accelerator (platform {d.platform!r}); a cell is "
            "measured on a TPU only (--rehearse walks it on the CPU and "
            "cannot print a result)")
    if len(devs) < chips:
        die(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs, peaks_for(d.device_kind)


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path, placed by the
    program's own ``enable_compilation_cache`` so that both write ONE
    directory: where ``JAX_COMPILATION_CACHE_DIR`` says, else
    ``<checkout>/.xla_cache``.  Every program is kept, however quickly it
    compiled, so that only a checkout's first run compiles."""
    import jax

    from pdnlp_tpu.utils.config import enable_compilation_cache

    path = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def work_dir(cell: str) -> str:
    """Scratch files of one cell (corpus, vocabulary, traces): inside the
    checkout, under the git-ignored ``output/``."""
    path = os.path.join(ROOT, "output", "benchmark", cell)
    os.makedirs(path, exist_ok=True)
    return path


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip, as the backend reports it:
    the allocator's high-water mark of live buffers plus the high-water mark
    of what it reserved for compiled programs' scratch.  (On a TPU
    ``peak_bytes_in_use`` leaves the programs' temporaries out; they are
    ``peak_bytes_reserved`` — read on the chip, PR 23: 3.16 GB beside a train
    step whose compiled temporaries are 3.07 GiB.)"""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"benchmark: memory_stats {d.id}: {stats}", file=sys.stderr)
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def percentile(values, p: float) -> float:
    """Nearest-rank percentile on the sorted sample (no interpolation past
    the data): the p-th of ``values``."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(0, min(len(xs) - 1, int(-(-p / 100.0 * len(xs) // 1)) - 1))
    return float(xs[k])


def latency_metric(name: str, samples: dict):
    """``<sample>_p<N>_ms`` -> the N-th percentile of ``samples[<sample>]``,
    or None where the name is not of that form or the sample is empty: an
    end-to-end latency metric is defined by its name in ``BENCHMARK.json``."""
    parts = name.split("_")
    if (len(parts) != 3 or parts[2] != "ms" or parts[1][:1] != "p"
            or not samples.get(parts[0])):
        return None
    return percentile(samples[parts[0]], float(parts[1][1:]))


class Checks:
    """The numbers compared with the reference, each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float, what: str = "",
            at_least: bool = False):
        ok = bool(value >= limit if at_least else value <= limit)
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "holds_if": ">=" if at_least else "<=",
                          "ok": ok, "what": what})
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def emit(self):
        for r in self.rows:
            say({"compared": r})


def emit_result(correct: bool, attempted: int, failed: int, metrics: dict,
                units: dict, device: dict, breakdown=None) -> None:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    say(line)


def say(obj: dict) -> None:
    """One JSON line on the REAL standard output (the program's own prints
    are redirected to stderr while a cell runs)."""
    print(json.dumps(obj), file=sys.__stdout__, flush=True)


def now() -> float:
    return time.perf_counter()
