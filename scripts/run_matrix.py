#!/usr/bin/env python
"""Run every strategy entrypoint once and collect the per-strategy table —
the analog of the reference's headline README table (reference README.md:10-20)
and of its all-checkpoints test.py/predict.py ritual (test.py:85-94).

Methodology (bench.py's, applied per row):
- every row fine-tunes bert-base from the in-repo two-phase pretrain
  checkpoint under the reference's 1-epoch constant-LR protocol (the
  reference's rows all start from pretrained hfl/chinese-bert-wwm-ext);
- ``--warmup_compile`` AOT-compiles the step programs BEFORE the timed
  epoch (the warm-CUDA-context analog), and the persistent compile cache
  (``utils.config.enable_compilation_cache``) carries compiled programs
  across rows/reruns;
- ``--probe_steps 30`` measures each row's steady-state hot-loop rate on
  re-fed batches before the epoch — the controlled per-strategy speed
  metric, free of the loader, eval and dispatch effects the epoch
  wall-clock is exposed to.  Compare strategies on the probe column; read
  the epoch column as end-to-end evidence;
- a row that dies on a transient runtime error (``DEADLINE_EXCEEDED``) is
  retried once.

One process per chip: every row is a child that takes the device, so this
parent stays off JAX until the last child has exited (the ``import jax`` in
``main`` comes after the loop).

Writes ONE artifact, ``output/matrix.json`` (meta + every row, including
each row's argv), and prints a markdown table from it.  The table this
script produced before PR 1 on v5e was removed with its record
(``results/matrix.json``) and has not been re-measured on this code.

    python scripts/run_matrix.py [--only row1,row2] [--out output/matrix.json]
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CKPT = "output/pretrained.msgpack"
PRETRAIN = ["--init_from", CKPT, "--init_head", "true"]
TIMED = ["--warmup_compile", "true", "--probe_steps", "30"]

CPU_ENV = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}

# (name, argv, env overrides, expected checkpoint, note)
RUNS = [
    ("single", [sys.executable, "single-tpu-cls.py", *PRETRAIN, *TIMED],
     {}, "output/single-cls.msgpack", "fp32, 288 steps"),
    ("dataparallel", [sys.executable, "multi-tpu-dataparallel-cls.py",
                      *PRETRAIN, *TIMED],
     {}, "output/dataparallel-cls.msgpack",
     "fp32; nn.DataParallel semantics (288 steps, global batch unscaled)"),
    ("dp (DDP analog)", [sys.executable, "multi-tpu-jax-cls.py",
                         *PRETRAIN, *TIMED],
     {}, "output/dp-cls.msgpack", "fp32, mesh data axis"),
    ("amp (bf16)", [sys.executable, "multi-tpu-amp-cls.py",
                    *PRETRAIN, *TIMED],
     {}, "output/amp-cls.msgpack", "bf16 compute, fp32 masters"),
    ("shardmap (Horovod analog)", [sys.executable, "multi-tpu-shardmap-cls.py",
                                   *PRETRAIN, *TIMED],
     {}, "output/shardmap-cls.msgpack", "explicit psum, bf16 grad wire"),
    ("zero (ZeRO-3 analog)", [sys.executable, "multi-tpu-zero-cls.py",
                              *PRETRAIN, *TIMED],
     {}, "output/zero-cls.msgpack", "fully-sharded state + remat"),
    ("zero + offload", [sys.executable, "multi-tpu-zero-cls.py",
                        "--offload_opt_state", "true",
                        "--ckpt_name", "offload-cls.msgpack",
                        *PRETRAIN, "--warmup_compile", "true"],
     {}, "output/offload-cls.msgpack",
     "Adam moments in host RAM; probe n/a (jnp.copy would un-offload)"),
    ("accelerate", [sys.executable, "multi-tpu-accelerate-cls.py",
                    *PRETRAIN, *TIMED],
     {}, "output/accelerate-cls.msgpack", "prepare() convenience API"),
    ("trainer (HF Trainer analog)", [sys.executable, "multi-tpu-trainer-cls.py",
                                     "--bf16", "true", *PRETRAIN],
     {}, None,
     "save/eval every 50 steps, bf16 rotation saves, best-model reload; "
     "six 205MB checkpoint fetches per epoch, so the row is repeated and "
     "its median reported", 3),
    ("sp (ring attention, seq 512)", [sys.executable, "multi-tpu-sp-cls.py",
                                      "--max_seq_len", "512",
                                      "--train_batch_size", "8",
                                      "--dev_batch_size", "8",
                                      "--dtype", "bfloat16",
                                      *PRETRAIN, *TIMED],
     {}, "output/sp-cls.msgpack",
     "4x sequence length, batch 8, 1150 steps, bf16"),
    ("moe (bert-base-moe, upcycled)", [sys.executable, "multi-tpu-moe-cls.py",
                                       "--dtype", "bfloat16",
                                       *PRETRAIN, *TIMED],
     {}, "output/ep-cls.msgpack",
     "4 experts upcycled from the dense pretrain, bf16"),
    # ---- CPU-mesh execution-evidence rows (multi-device-only paths on the
    # one-chip image; loss/param parity pinned by tests/) ----
    ("spawn 2-proc (CPU backend)",
     [sys.executable, "multi-tpu-spawn-cls.py", "--num_processes", "2",
      "--model", "bert-small", "--data_limit", "2000", "--ckpt_name",
      "spawn-cls.msgpack"],
     {**CPU_ENV, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
     "output/spawn-cls.msgpack",
     "2 real processes x 4 virtual devices, TCP rendezvous, bert-small; "
     "cross-process zero/pp execution pinned by tests/test_spawn.py"),
    ("tp 4x2 data*model (CPU mesh)",
     [sys.executable, "multi-tpu-tp-cls.py", "--model", "bert-tiny",
      "--max_seq_len", "64", "--data_limit", "2000",
      "--mesh_shape", '{"data": 4, "model": 2}',
      "--log_every", "1000000", "--ckpt_name", "tp-cls.msgpack"],
     CPU_ENV, "output/tp-cls.msgpack", "bert-tiny execution evidence"),
    ("pp 2-stage (CPU mesh)",
     [sys.executable, "multi-tpu-pp-cls.py", "--model", "bert-tiny",
      "--max_seq_len", "64", "--data_limit", "2000",
      "--mesh_shape", '{"stage": 2}', "--num_devices", "2",
      "--microbatches", "4",
      "--log_every", "1000000", "--ckpt_name", "pp-cls.msgpack"],
     CPU_ENV, "output/pp-cls.msgpack", "bert-tiny execution evidence"),
]

RE_MIN = re.compile(r"耗时：([\d.]+)分钟")
RE_ACC = re.compile(r"accuracy：([\d.]+)")
RE_PROBE = re.compile(r"probe steps/s：([\d.]+)")
RE_EVAL_ACC = re.compile(r"eval_accuracy ([\d.]+)")
RE_RUNTIME = re.compile(r"'train_runtime': ([\d.]+)")
TRANSIENT = ("DEADLINE_EXCEEDED",)


def run_row(name, argv, env_over, ckpt_path, note, timeout, repeat=1):
    """One strategy row.  ``repeat`` > 1 re-runs the command back-to-back and
    reports the MEDIAN minutes (each attempt kept in ``runs_min``) — used for
    the trainer row, whose checkpoint fetches make it the noisiest."""
    if repeat > 1:
        rows = [run_row(name, argv, env_over, ckpt_path, note, timeout)
                for _ in range(repeat)]
        ok = [r for r in rows if "error" not in r]
        if not ok:  # all attempts failed: ship an honestly-labeled error row
            err = rows[0]
            err["note"] = (f"all {repeat} back-to-back attempts failed; "
                           + err.get("note", note))
            return err
        ok.sort(key=lambda r: r.get("minutes") or 1e9)
        # lower median for even survivor counts: a failed attempt must not
        # flip the published number to the slower (max) of two survivors
        med = ok[(len(ok) - 1) // 2]
        med["runs_min"] = [r.get("minutes") for r in ok]
        med["note"] = (f"median of {len(ok)}/{repeat} successful "
                       f"back-to-back runs; " + med["note"])
        return med
    env = dict(os.environ, **env_over)
    print(f"=== {name}: {' '.join(argv[1:])}", flush=True)
    for attempt in (1, 2):
        t0 = time.time()
        try:
            p = subprocess.run(argv, env=env, capture_output=True, text=True,
                               timeout=timeout)
        except subprocess.TimeoutExpired:
            print("    -> TIMEOUT", flush=True)
            return {"error": f"timeout after {timeout}s", "note": note,
                    "argv": argv[1:]}
        out = p.stdout + p.stderr
        if p.returncode == 0:
            break
        if attempt == 1 and any(t in out for t in TRANSIENT):
            print(f"    -> transient failure (rc {p.returncode}), retrying",
                  flush=True)
            continue
        print(out[-3000:])
        return {"error": p.returncode, "note": note, "argv": argv[1:]}
    minutes = RE_MIN.findall(out)
    accs = RE_ACC.findall(out)
    probes = RE_PROBE.findall(out)
    eval_accs = RE_EVAL_ACC.findall(out)
    runtime = RE_RUNTIME.findall(out)
    row = {
        "minutes": float(minutes[-1]) if minutes else (
            round(float(runtime[-1]) / 60, 4) if runtime else None),
        "probe_steps_per_sec": float(probes[-1]) if probes else None,
        "accuracy": float(accs[-1]) if accs else (
            float(eval_accs[-1]) if eval_accs else None),
        "checkpoint": ckpt_path if ckpt_path and os.path.exists(ckpt_path)
        else ("missing!" if ckpt_path else "output/auto/checkpoint-*"),
        "wall_s_incl_startup": round(time.time() - t0, 1),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": note,
        "argv": argv[1:],
    }
    print(f"    -> {row['minutes']} min, probe "
          f"{row['probe_steps_per_sec']} steps/s, acc {row['accuracy']}",
          flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of row names to run "
                         "(others keep their existing matrix.json entry)")
    ap.add_argument("--out", default="output/matrix.json")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    os.chdir(ROOT)
    if not os.path.exists(CKPT):
        sys.exit(f"{CKPT} missing — run pretrain-tpu.py first")

    results = {}
    if args.only and os.path.exists(args.out):
        with open(args.out) as f:
            prior = json.load(f)
        # accept both the current {"meta":…, "rows":…} artifact and the
        # legacy flat {row: …} format, so --only never discards old rows
        results = prior.get("rows") if "rows" in prior else {
            k: v for k, v in prior.items() if k != "meta"}
    wanted = [w.strip() for w in args.only.split(",")] if args.only else None
    fresh = set()
    for name, argv, env_over, ckpt_path, note, *rest in RUNS:
        if wanted and not any(w in name for w in wanted):
            continue
        results[name] = run_row(name, argv, env_over, ckpt_path, note,
                                args.timeout, repeat=rest[0] if rest else 1)
        fresh.add(name)
    # carried-over rows were measured under a (possibly different) earlier
    # session/protocol — stamp them so the single meta.protocol block can't
    # silently claim one methodology for rows it didn't produce
    for name, row in results.items():
        if isinstance(row, dict):
            row.pop("carried_over", None)
            if name not in fresh:
                row["carried_over"] = True

    import jax  # only now: every child that needed the chip has exited

    artifact = {
        "meta": {
            "device": str(jax.devices()[0].device_kind),
            "platform": jax.devices()[0].platform,
            "protocol": ("1 epoch, constant LR 3e-5, batch 32 (sp: 8), "
                         "seq 128 (sp: 512), init_from "
                         "output/pretrained.msgpack + --init_head, dev off; "
                         "epoch timed after AOT compile (warmup_compile), "
                         "probe = 30 re-fed steps before the epoch"),
            "written_by": "scripts/run_matrix.py",
        },
        "rows": results,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2, ensure_ascii=False)
    print(f"\nwrote {args.out}")

    def table(rows):
        print("\n| Strategy | min/epoch (post-compile) | probe steps/s | dev accuracy |")
        print("|---|---|---|---|")
        for name, row in rows:
            if "error" in row:
                print(f"| {name} | FAILED: {row['error']} | — | — |")
            else:
                probe = (f"{row['probe_steps_per_sec']:.1f}"
                         if row.get("probe_steps_per_sec") else "—")
                mins = (f"{row['minutes']:.3f}"
                        if row.get("minutes") is not None else "—")
                acc = (f"{row['accuracy']:.4f}"
                       if row.get("accuracy") is not None else "—")
                stale = " (carried over)" if row.get("carried_over") else ""
                print(f"| {name}{stale} | {mins} | {probe} | {acc} |")

    # the CPU-mesh rows are execution evidence for multi-device-only paths
    # (smaller models, data_limit) — never mix them into the TPU comparison
    main_rows = [(n, r) for n, r in results.items() if "CPU" not in n]
    ev_rows = [(n, r) for n, r in results.items() if "CPU" in n]
    table(main_rows)
    if ev_rows:
        print("\nExecution evidence (CPU virtual mesh, reduced model/data — "
              "not comparable to the TPU rows above):")
        table(ev_rows)


if __name__ == "__main__":
    main()
