#!/usr/bin/env python
"""MLM-only ceiling sweep — the machine-readable artifact behind the
README's accuracy table rows that warm-start from the UNLABELED-text-only
pretrain (no supervised stage).

The reference's 0.57 comes from externally pretrained weights
(~5.4B tokens); the in-repo MLM stage sees only the ~1.5M-token corpus.
This sweep fine-tunes the SAME MLM trunk (``output/pretrained-mlm.msgpack``,
150 epochs @ mask 0.30 — the measured plateau of the epochs/mask grid:
0.476-0.4875 across 50/100/150/300 epochs at masks 0.15/0.30) under a grid
of fine-tune recipes, and writes ``output/mlm_only_sweep.json``.  Whatever
the best cell says IS the measured MLM-only ceiling of this corpus.

    python scripts/sweep_mlm_only.py
"""
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MLM = "output/pretrained-mlm.msgpack"
OUT = "output/mlm_only_sweep.json"

# (label, extra argv) — all rows: bf16, dp, warm start from the MLM trunk
GRID = [
    ("1ep-constLR (reference exact protocol)", ["--epochs", "1"]),
    ("2ep-warmup_linear (shipped recipe)",
     ["--epochs", "2", "--lr_schedule", "warmup_linear"]),
    ("3ep-warmup_linear",
     ["--epochs", "3", "--lr_schedule", "warmup_linear"]),
    ("5ep-warmup_linear",
     ["--epochs", "5", "--lr_schedule", "warmup_linear"]),
    ("3ep-warmup_linear-lr2e-5",
     ["--epochs", "3", "--lr_schedule", "warmup_linear",
      "--learning_rate", "2e-5"]),
]

RE_ACC = re.compile(r"accuracy：([\d.]+)")


def main() -> None:
    os.chdir(ROOT)
    if not os.path.exists(MLM):
        sys.exit(f"{MLM} missing — run pretrain-tpu.py first")
    rows = {}
    for label, extra in GRID:
        argv = [sys.executable, "multi-tpu-jax-cls.py", "--dtype", "bfloat16",
                "--init_from", MLM, "--ckpt_name", "mlm-sweep-tmp.msgpack",
                "--log_every", "1000000", "--warmup_compile", "true", *extra]
        print(f"=== {label}", flush=True)
        t0 = time.time()
        p = subprocess.run(argv, capture_output=True, text=True, timeout=1800)
        out = p.stdout + p.stderr
        if p.returncode != 0:
            print(out[-2000:])
            rows[label] = {"error": p.returncode, "argv": argv[1:]}
            continue
        accs = RE_ACC.findall(out)
        rows[label] = {"accuracy": float(accs[-1]) if accs else None,
                       "wall_s": round(time.time() - t0, 1),
                       "argv": argv[1:]}
        print(f"    -> {rows[label]}", flush=True)
    best = max((r["accuracy"] for r in rows.values()
                if r.get("accuracy") is not None), default=None)
    artifact = {
        "meta": {"trunk": MLM,
                 "trunk_recipe": "150 epochs packed MLM, span mask 0.30 "
                                 "(plateau of the 50-300 epoch x mask "
                                 "0.15/0.30 grid: 0.476-0.4875 under the "
                                 "1-epoch protocol)",
                 "mlm_only_best": best,
                 "written_by": "scripts/sweep_mlm_only.py"},
        "rows": rows,
    }
    with open(OUT, "w") as f:
        json.dump(artifact, f, indent=2, ensure_ascii=False)
    print(f"\nwrote {OUT}; MLM-only best = {best}")


if __name__ == "__main__":
    main()
