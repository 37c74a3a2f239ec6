#!/usr/bin/env python
"""Long-context TRAINING measurements (VERDICT r4 item 4).

Runs real fused train steps on ``bert-base-long`` (2048-position table) at
seq 1024/2048 on the chip — remat on, bf16, XLA vs the pallas flash kernel —
and records steps/s, tokens/s, and peak HBM.  This is the full-step number
the op-level flash table (README) could not give: the crossover claim for
training comes from here.

Writes/merges ``results/longcontext.json``.  The rows this script measured
before PR 1 on v5e were removed with their record and have not been
re-measured on this code; a chip run recreates the file.

    python scripts/bench_longcontext.py [name-substring ...]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATH = os.path.join(REPO, "results", "longcontext.json")

CODE = r"""
import json, sys, time
spec = json.loads(sys.argv[1])
import jax, jax.numpy as jnp
from pdnlp_tpu.utils.config import enable_compilation_cache
enable_compilation_cache()
from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.utils.config import Args
args = Args(**spec['args'])
tr, tl, _ = build_parallel_trainer(args, mode='dp')
batch = tr.put(next(iter(tl)))
state = jax.tree_util.tree_map(jnp.copy, tr.state)
for _ in range(3):
    state, m = tr.train_step(state, batch)
float(jax.device_get(m['loss']))
n = spec.get('steps', 20)
t0 = time.time()
for _ in range(n):
    state, m = tr.train_step(state, batch)
float(jax.device_get(m['loss']))
dt = time.time() - t0
stats = jax.devices()[0].memory_stats() or {}
print(json.dumps({
    'steps_per_sec': round(n / dt, 3),
    'tokens_per_sec': round(n / dt * args.train_batch_size * args.max_seq_len),
    'peak_hbm_gb': round(stats.get('peak_bytes_in_use', 0) / 2**30, 2),
    'loss': round(float(jax.device_get(m['loss'])), 4),
}))
"""


def run(name, seq, batch, attn, remat=True, extra=None):
    # attn_dropout=0 on EVERY row: probability dropout forces the XLA
    # attention path (ops/attention.py), so a "pallas" row with the default
    # 0.1 would silently measure XLA — and the xla/flash comparison must
    # train the same model anyway
    args = dict(strategy="dp", model="bert-base-long", dtype="bfloat16",
                max_seq_len=seq, train_batch_size=batch, dev_batch_size=batch,
                remat=remat, attention_impl=attn, log_every=10 ** 9,
                data_limit=2000, attn_dropout=0.0)
    args.update(extra or {})
    out = subprocess.run(
        [sys.executable, "-c", CODE,
         json.dumps({"args": args, "steps": 20})],
        capture_output=True, text=True, cwd=REPO)
    if out.returncode != 0:
        print(f"{name}: FAILED\n{out.stderr[-2500:]}", file=sys.stderr)
        return {"error": out.stderr.strip().splitlines()[-1][:300]
                if out.stderr.strip() else "unknown"}
    r = json.loads(out.stdout.strip().splitlines()[-1])
    r["config"] = {"seq": seq, "batch": batch, "attention_impl": attn,
                   "remat": remat, **(extra or {})}
    print(f"{name}: {r['steps_per_sec']} steps/s, {r['tokens_per_sec']} tok/s,"
          f" peak {r['peak_hbm_gb']} GB", file=sys.stderr)
    return r


def _dump(res, path=PATH):
    """Atomic artifact write: an interrupt mid-dump must not eat the
    previously measured (minutes-of-chip-time) rows."""
    tmp = path + ".tmp"
    json.dump(res, open(tmp, "w"), indent=2)
    os.replace(tmp, path)


def merge_rows(new_rows, path=PATH, device=None):
    """Merge freshly measured rows into ``results/longcontext.json``
    WITHOUT clobbering history: an existing row without an ``"error"``
    key is never overwritten (measured rows are minutes of chip time; a
    CPU smoke re-run must not eat them) — only error rows and
    new names take the incoming value.  ``meta.device`` is only stamped
    when absent, for the same reason.  Returns the merged dict (also
    written to ``path``) and the list of row names actually merged —
    ``bench.py --longcontext`` funnels its smoke rows through here, and
    the non-clobber property is pinned by ``tests/test_longcontext.py``.
    """
    res = json.load(open(path)) if os.path.exists(path) else {}
    res.setdefault("meta", {})
    res.setdefault("rows", {})
    merged = []
    for name, row in new_rows.items():
        old = res["rows"].get(name)
        if old is not None and "error" not in old:
            continue  # history wins
        res["rows"][name] = row
        merged.append(name)
    if device and "device" not in res["meta"]:
        res["meta"]["device"] = device
    _dump(res, path)
    return res, merged


def main():
    res = json.load(open(PATH)) if os.path.exists(PATH) else {}
    res.setdefault("meta", {
        "model": "bert-base-long (2048-position table, models/config.py)",
        "protocol": "20 re-fed fused train steps (fwd+bwd+AdamW) after 3 "
                    "warmup, bf16, remat on, single chip; tokens/s = "
                    "steps/s * batch * seq",
    })
    res.setdefault("rows", {})
    grid = {
        "seq512_b16_xla": (512, 16, "xla"),
        "seq512_b16_flash": (512, 16, "pallas"),
        "seq1024_b8_xla": (1024, 8, "xla"),
        "seq1024_b8_flash": (1024, 8, "pallas"),
        "seq2048_b4_xla": (2048, 4, "xla"),
        "seq2048_b4_flash": (2048, 4, "pallas"),
        "seq2048_b4_xla_noremat": (2048, 4, "xla", False),
        # the r5 headline's activation lever at long sequence: GELU share
        # of the step shrinks as O(S^2) attention grows, so the gain
        # should taper vs the +7% measured at seq 128
        "seq1024_b8_xla_tanh": (1024, 8, "xla", True, {"gelu": "tanh"}),
        "seq2048_b4_xla_tanh": (2048, 4, "xla", True, {"gelu": "tanh"}),
    }
    # space- or comma-separated substrings; a token that exactly names a
    # row selects ONLY that row (so "seq1024_b8_xla" can't silently drag
    # in its "_tanh" substring-superset sibling)
    only = [t for a in sys.argv[1:] for t in a.split(",") if t]

    def selected(name):
        if not only:
            return True
        if any(o == name for o in only):
            return True
        return any(o in name and o not in grid for o in only)

    for name, spec in grid.items():
        if not selected(name):
            continue
        if name in res["rows"] and "error" not in res["rows"][name]:
            continue
        row = run(name, *spec)
        if "error" in row:
            # one retry: first-touch chip init / compile-cache races are
            # the observed transient class; a second error is real
            print(f"{name}: retrying once after error", file=sys.stderr)
            row = run(name, *spec)
        res["rows"][name] = row
        _dump(res)

    # the sequence-parallel path at 1024: the sp entrypoint itself (ring
    # attention inside shard_map; seq axis 1 on the one-chip image — the
    # ring's multi-shard parity is pinned by tests/test_sp.py and the
    # cross-process spawn test), probe = the controlled metric
    name = "sp_seq1024_b8_ring"
    if selected(name) and (
            name not in res["rows"] or "error" in res["rows"][name]):
        import re

        argv = [sys.executable, "multi-tpu-sp-cls.py", "--model",
                "bert-base-long", "--max_seq_len", "1024",
                "--train_batch_size", "8", "--dev_batch_size", "8",
                "--dtype", "bfloat16", "--attn_dropout", "0.0",
                "--data_limit", "2000", "--remat", "true",
                "--warmup_compile", "true", "--probe_steps", "20",
                "--log_every", "1000000"]
        out = subprocess.run(argv, capture_output=True, text=True, cwd=REPO)
        text = out.stdout + out.stderr
        probe = re.findall(r"probe steps/s：([\d.]+)", text)
        mins = re.findall(r"耗时：([\d.]+)分钟", text)
        row = ({"steps_per_sec": float(probe[-1]),
                "tokens_per_sec": round(float(probe[-1]) * 8 * 1024),
                "epoch_minutes": float(mins[-1]) if mins else None,
                "config": {"seq": 1024, "batch": 8, "impl": "ring(shard_map)",
                           "remat": True, "argv": argv[1:]}}
               if out.returncode == 0 and probe else
               {"error": text.strip().splitlines()[-1][:300]})
        res["rows"][name] = row
        print(f"{name}: {row}", file=sys.stderr)

    try:
        import jax

        res["meta"]["device"] = jax.devices()[0].device_kind
    except Exception:
        pass
    _dump(res)
    print(json.dumps(res["rows"], indent=2))


if __name__ == "__main__":
    main()
