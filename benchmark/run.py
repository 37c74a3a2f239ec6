"""One run of one cell:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see ``README.md``).  Without
a TPU, with fewer chips than the cell asks for, or with a device kind that
``peaks.json`` does not list, the run ends non-zero and prints no result.
``--rehearse`` walks the same code on the CPU at the configuration's tiny
size to find wrong paths and arguments; it prints no result line and always
ends with code 3.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import os
import shutil
import sys

if __package__ in (None, ""):       # `python benchmark/run.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common, reducers  # noqa: E402


class Context:
    """What a traffic kind gets besides its cell."""

    def __init__(self, ns, cell, devices, peaks):
        self.seed, self.seconds = int(ns.seed), float(ns.seconds)
        self.trace, self.rehearse = bool(int(ns.trace)), bool(ns.rehearse)
        self.devices, self.peaks = devices[: cell.chips], peaks
        self._dir = os.path.join(common.work_dir(cell.name), "trace")

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def start_trace(self, python_tracer: bool = True):
        """``python_tracer=False`` (the serving kinds): the profiler's hook
        on every Python call stays off.  The device's operations and the
        ``bench:`` annotations are all that is read back, and the hook
        charges every call of a worker's per-row loops: a traced server lost
        a third of its rate under it (PERF.md section 6, PR 34).  The train
        kind keeps the profiler's default, the instrument its series has."""
        if not self.trace:
            return None
        import jax

        shutil.rmtree(self._dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        if not python_tracer:
            options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=options)
        return (self._dir, common.now())

    def stop_trace(self, handle):
        """-> the trace's summary; its window runs from the moment tracing
        was on to the moment it is turned off."""
        if handle is None:
            return None
        import jax

        from benchmark.trace import xplane

        path, began = handle
        window_s = common.now() - began
        jax.profiler.stop_trace()
        summary = xplane.summarize(xplane.load(xplane.find_xplane(path)),
                                   window_s)
        shutil.rmtree(path, ignore_errors=True)
        return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args(argv)
    return run_cell(ns)


def run_cell(ns) -> int:
    cell = common.Cell(ns.workload)
    if not os.path.isdir(os.path.join(common.ROOT, "pdnlp_tpu")):
        common.die("the program (pdnlp_tpu/) is not in this directory")
    import jax

    if not ns.rehearse:
        common.enable_cache()
    devices, peaks = common.find_devices(cell.chips, ns.rehearse)
    ctx = Context(ns, cell, devices, peaks)
    kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
    # whatever the program prints goes to stderr; stdout carries the
    # benchmark's lines only
    with contextlib.redirect_stdout(sys.stderr):
        result = kind.run(cell, ctx)
    ns.result = result
    if ns.rehearse:
        print(f"benchmark: rehearsal of {cell.name} done (correct="
              f"{result['correct']}); a rehearsal is not a measurement",
              file=sys.stderr)
        return common.REHEARSAL_EXIT
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    obs = result["obs"]
    if ctx.trace:
        trace = obs["trace"]
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        wanted, values = cell.per_layer(), {}
        for m in wanted:
            v = reducers.read_metric(m["name"], obs, cell.dir)
            if v is not None:
                values[m["name"]] = v
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": trace["idle_gaps"]}
    else:
        wanted = cell.end_to_end()
        values = {m["name"]: result["end_to_end"][m["name"]] for m in wanted}
        breakdown = None
    units = {m["name"]: m["unit"] for m in wanted}
    common.emit_result(result["correct"], result["attempted"],
                       result["failed"], values, units, device, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
