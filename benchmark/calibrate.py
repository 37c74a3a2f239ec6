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
    python -m benchmark.calibrate sweep <cell> <seconds> [seed=<n>] <rate> ...
        one short run per session rate (seeds <n>, <n>+1, ...; 1000 unless
        given); prints tails, the backlog's growth, the rows seated and the
        generator's lateness; stops after the first rate past the knee
        (requests failed, or the second half's median time to first token
        15 % and 10 ms above the first's).
    python -m benchmark.calibrate probe-slots <cell> <seconds> <slots> ...
        a closed-loop cell once a slot count (as many clients): tokens/s,
        a plain round, the window's thirds and peak memory.

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


def probe_slots(cell_name, seconds, slots):
    """Slots (= clients) against tokens/s and peak memory."""
    import importlib

    for k, n in enumerate(slots):
        cell = common.Cell(cell_name)
        cell.config["assumed"]["slots"] = cell.traffic["clients"] = n
        kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
        res = kind.run(cell, _ctx(cell, 3000 + k, seconds))
        c = res["obs"]["counters"]
        common.say({"slots": n, "memory_peak_bytes": res["memory_peak_bytes"],
                    "end_to_end": res["end_to_end"], "failed": res["failed"],
                    "thirds": res["obs"]["thirds"],
                    "decode_steps_per_s": c["decode_steps"] / c["window_s"],
                    "checks": {r["check"]: r["value"] for r in res["checks"]}})


def sweep(cell_name, seconds, rates, seed=1000):
    import importlib

    for k, rate in enumerate(rates):
        cell = common.Cell(cell_name)
        cell.traffic["session_rate_per_s"] = rate
        ctx = _ctx(cell, seed + k, seconds)
        kind = importlib.import_module("benchmark.kinds." + cell.traffic["kind"])
        res = kind.run(cell, ctx)
        c = res["obs"]["counters"]
        s = res["obs"]["samples"]
        half = len(s["ttft_ms"]) // 2
        first = common.percentile(s["ttft_ms"][:half], 50)
        second = common.percentile(s["ttft_ms"][half:], 50)
        common.say({"session_rate_per_s": rate, "end_to_end": res["end_to_end"],
                    "failed": res["failed"], "requests": len(s["ttft_ms"]),
                    "ttft_p50_first_half_ms": first,
                    "ttft_p50_second_half_ms": second,
                    "gen_lateness_p99_ms": common.percentile(s["lateness_ms"], 99),
                    "tokens_per_s": c["tokens_seen"] / c["window_s"],
                    "slot_occupancy": c["live_rows_sum"] / max(1, c["slot_steps"]),
                    "rows_seated": c["live_rows_sum"] / max(1, c["occupancy_n"]),
                    "decode_steps_per_s": c["decode_steps"] / c["window_s"]})
        # below the knee the halves' medians agree to a tenth; a queue that
        # fills from the ramp's start on makes them 1.67 to 1 at most (their
        # midpoints' times since the start at a 15 s ramp), and one that
        # fills slowly read 1.23 (PERF.md section 6, PR 34): "twice" never
        # fires.  10 ms keeps the clock's grain out at the lowest rates
        if res["failed"] or second > max(1.15 * first, first + 10.0):
            common.say({"past_the_knee_at": rate})
            break


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
        elif what == "probe-slots":
            probe_slots(argv[0], float(argv[1]), [int(x) for x in argv[2:]])
        elif what == "sweep":
            seed = int(argv.pop(2)[5:]) if argv[2].startswith("seed=") else 1000
            sweep(argv[0], float(argv[1]), [float(x) for x in argv[2:]], seed)
        else:
            raise SystemExit(__doc__)


if __name__ == "__main__":
    main()
