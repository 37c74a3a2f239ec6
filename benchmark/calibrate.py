"""What the builder of a benchmark runs on the chip before fixing a number:
the open loop's knee, and the readings every limit of ``correct`` is set
from.  Not part of a run; the driver never calls it.

    python -m benchmark.calibrate readings-train <cell> <seed> [<seed> ...]
        per seed: the program's numbers against the reference; for the
        first three seeds also the control's (the reference in fp8 put in
        the program's place, under the same dropout masks).
    python -m benchmark.calibrate readings-serve <cell> <seconds> <seed> ...
        per seed: the served tokens' widest gap, and the control's.
    python -m benchmark.calibrate probe-train <cell> <batch> [<batch> ...]
        per-chip batch against peak memory and rate.
    python -m benchmark.calibrate sweep <cell> <seconds> <rate> [<rate> ...]
        one short run per session rate; prints tails and the backlog's growth.

Each seed or rate is a run of the cell's own kind in this process, one after
another, so that the compile cache is shared.
"""
from __future__ import annotations

import argparse
import sys

from benchmark import common
from benchmark.run import Context


def _ctx(cell, seed, seconds):
    ns = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse=False)
    devices, peaks = common.find_devices(cell.chips, False)
    return Context(ns, cell, devices, peaks)


def readings_train(cell_name, seeds, controls=3):
    from benchmark.kinds import train_epochs
    from benchmark.reference import model, weights

    for k, seed in enumerate(seeds):
        cell = common.Cell(cell_name)
        ctx = _ctx(cell, seed, 1.0)
        keep = {}
        orig = train_epochs.compare

        def spy(first, seed_, sizes, recipe, limits, prec="f32"):
            keep.update(first=first, sizes=sizes, recipe=recipe, limits=limits)
            return orig(first, seed_, sizes, recipe, limits, prec)

        train_epochs.compare = spy
        try:
            res = train_epochs.run(cell, ctx)
        finally:
            train_epochs.compare = orig
        first, sizes, recipe = keep["first"], keep["sizes"], keep["recipe"]
        # the control: the reference in fp8 in the program's place, judged
        # against the float32 reference exactly as the program is
        w = weights.make_weights(seed, sizes)
        host, n = first["batch"], first["n"]
        batches = [{k: v[i] for k, v in host.items()} for i in range(n)] \
            if host["input_ids"].ndim == 3 else [host]
        kw = dict(heads=sizes["num_attention_heads"], eps=sizes["layer_norm_eps"])
        out = {"seed": seed, "program": {r["check"]: r["value"] for r in res["checks"]},
               "tokens_per_s": res["end_to_end"]["train_tokens_per_s"]}
        for prec in ("fp8",) if k < controls else ():
            losses, mu, p = model.train_steps(w, batches, recipe, prec=prec,
                                              dropout=recipe.get("dropout"), **kw)
            ctl = {"batch": host, "n": n, "losses": losses,
                   "mu": model.leaf_norms(mu),
                   "delta": model.leaf_norms({k: p[k] - w[k] for k in p})}
            rows = orig(ctl, seed, sizes, recipe, keep["limits"]).rows
            out["reference_in_" + prec] = {r["check"]: r["value"] for r in rows}
        common.say(out)


def readings_serve(cell_name, seconds, seeds):
    import importlib

    from benchmark.kinds import serve

    for seed in seeds:
        cell = common.Cell(cell_name)
        ctx = _ctx(cell, seed, seconds)
        keep = {}
        orig = serve.compare

        def spy(served, seed_, sizes, banned, limits, prec="f32"):
            keep.update(served=served, sizes=sizes, banned=banned)
            return orig(served, seed_, sizes, banned, limits, prec)

        serve.compare = spy
        try:
            kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
            res = kind.run(cell, ctx)
        finally:
            serve.compare = orig
        out = {"seed": seed, "program": {r["check"]: r["value"] for r in res["checks"]},
               "end_to_end": res["end_to_end"], "failed": res["failed"]}
        for prec in ("bf16", "fp8"):
            gaps = serve.reference_gaps(keep["served"], seed, keep["sizes"],
                                        keep["banned"], lowprec=prec)
            flat = sorted(g for gs in gaps for g in gs)
            out["reference_in_" + prec] = {
                "widest_gap": flat[-1], "tokens": len(flat),
                "share_not_best": sum(1 for g in flat if g > 0) / len(flat)}
        common.say(out)


def probe_train(cell_name, batches):
    """Per-chip batch against memory and rate (2 s windows)."""
    from benchmark.kinds import train_epochs

    for k, b in enumerate(batches):
        cell = common.Cell(cell_name)
        cell.config["program"]["train_batch_size"] = b
        res = train_epochs.run(cell, _ctx(cell, 2000 + k, 2.0))
        c = res["obs"]["counters"]
        common.say({"train_batch_size": b, "memory_peak_bytes": res["memory_peak_bytes"],
                    "tokens_per_s": res["end_to_end"]["train_tokens_per_s"],
                    "step_ms": 1e3 * c["window_s"] / c["steps"],
                    "checks": {r["check"]: r["value"] for r in res["checks"]}})


def sweep(cell_name, seconds, rates):
    import importlib

    for k, rate in enumerate(rates):
        cell = common.Cell(cell_name)
        cell.traffic["session_rate_per_s"] = rate
        ctx = _ctx(cell, 1000 + k, seconds)
        kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
        res = kind.run(cell, ctx)
        c = res["obs"]["counters"]
        s = res["obs"]["samples"]
        half = len(s["ttft_ms"]) // 2
        common.say({"session_rate_per_s": rate, "end_to_end": res["end_to_end"],
                    "failed": res["failed"], "requests": len(s["ttft_ms"]),
                    "ttft_p50_first_half_ms": common.percentile(s["ttft_ms"][:half], 50),
                    "ttft_p50_second_half_ms": common.percentile(s["ttft_ms"][half:], 50),
                    "tokens_per_s": c["tokens_seen"] / c["window_s"],
                    "slot_occupancy": c["live_rows_sum"] / max(1, c["slot_steps"]),
                    "decode_steps_per_s": c["decode_steps"] / c["window_s"]})


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    common.enable_cache()
    what = argv.pop(0)
    import contextlib

    with contextlib.redirect_stdout(sys.stderr):
        if what == "readings-train":
            readings_train(argv[0], [int(x) for x in argv[1:]])
        elif what == "readings-serve":
            readings_serve(argv[0], float(argv[1]), [int(x) for x in argv[2:]])
        elif what == "probe-train":
            probe_train(argv[0], [int(x) for x in argv[1:]])
        elif what == "sweep":
            sweep(argv[0], float(argv[1]), [float(x) for x in argv[2:]])
        else:
            raise SystemExit(__doc__)


if __name__ == "__main__":
    main()
