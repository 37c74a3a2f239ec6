#!/usr/bin/env python
"""Ablation + XProf profile of the hot train step (VERDICT r4 item 2).

Measures steps/s for the bench config and one-knob ablations (EMA off,
dropout off, fused vs split QKV, eval forward), captures an XProf trace of
the base step, and parses the trace's op-level table into the top time
sinks.  Writes ``results/profile_r05.json``.

Run on the real chip:  python scripts/profile_step.py
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def probe(args_kw, env=None, steps=30, trace_dir=None):
    """Fresh-process probe: build trainer, compile, time `steps` re-fed
    steps.  A subprocess per variant keeps XLA/env state independent."""
    import subprocess

    payload = json.dumps({"args": args_kw, "steps": steps,
                          "trace_dir": trace_dir})
    code = (
        "import json,sys,time\n"
        "spec=json.loads(sys.argv[1])\n"
        "import jax, jax.numpy as jnp\n"
        "from pdnlp_tpu.train.run import build_parallel_trainer\n"
        "from pdnlp_tpu.utils.config import Args, enable_compilation_cache\n"
        "enable_compilation_cache()\n"
        "args=Args(**spec['args'])\n"
        "tr,tl,_=build_parallel_trainer(args,mode='dp')\n"
        "batch=tr.put(next(iter(tl)))\n"
        "state=jax.tree_util.tree_map(jnp.copy,tr.state)\n"
        "for _ in range(3): state,m=tr.train_step(state,batch)\n"
        "float(jax.device_get(m['loss']))\n"
        "td=spec['trace_dir']\n"
        "if td: jax.profiler.start_trace(td)\n"
        "t0=time.time()\n"
        "for _ in range(spec['steps']): state,m=tr.train_step(state,batch)\n"
        "float(jax.device_get(m['loss']))\n"
        "dt=time.time()-t0\n"
        "if td: jax.profiler.stop_trace()\n"
        "ev=tr.eval_step\n"
        "p=state['params']\n"
        "for _ in range(3): r=ev(p,batch)\n"
        "float(jax.device_get(r['loss_sum']))\n"
        "t0=time.time()\n"
        "for _ in range(spec['steps']): r=ev(p,batch)\n"
        "float(jax.device_get(r['loss_sum']))\n"
        "de=time.time()-t0\n"
        "print(json.dumps({'steps_per_sec':spec['steps']/dt,"
        "'eval_steps_per_sec':spec['steps']/de}))\n"
    )
    e = dict(os.environ)
    e.update(env or {})
    out = subprocess.run([sys.executable, "-c", code, payload], env=e,
                         capture_output=True, text=True, cwd=REPO)
    if out.returncode != 0:
        print(out.stderr[-3000:], file=sys.stderr)
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def parse_trace(trace_dir, steps=30):
    """Aggregate the TPU "XLA Ops" track of the Chrome trace jax.profiler
    writes (``*.trace.json.gz``) into per-op-family time.  (The xplane.pb
    route needs a tensorboard_plugin_profile matching the installed TF —
    absent here; the Chrome trace carries the same device timeline.)"""
    import collections
    import glob
    import gzip
    import re
    import shutil

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not paths:
        return {"error": "no trace.json.gz produced"}
    try:
        d = json.load(gzip.open(paths[-1]))
        evs = d["traceEvents"]
        dev_pid = next((e["pid"] for e in evs
                        if e.get("ph") == "M" and e.get("name") == "process_name"
                        and "TPU" in e["args"].get("name", "")), None)
        tids = {e["tid"]: e["args"].get("name", "") for e in evs
                if e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["pid"] == dev_pid}
        fam = collections.defaultdict(float)
        cnt = collections.Counter()
        for e in evs:
            if (e.get("ph") == "X" and e["pid"] == dev_pid
                    and tids.get(e["tid"]) == "XLA Ops"):
                name = re.sub(r"\.\d+$", "", e["name"])
                fam[name] += e.get("dur", 0)
                cnt[name] += 1
        tot = sum(fam.values()) or 1.0
        keep = os.path.join(REPO, "results", "xprof_base_step.trace.json.gz")
        shutil.copy(paths[-1], keep)
        return {
            "source": "results/xprof_base_step.trace.json.gz "
                      f"(jax.profiler, {steps}-step window, base step)",
            "device_ms_per_step": round(tot / (steps * 1e3), 2),
            "op_families": [
                {"family": n, "ms_per_step": round(v / (steps * 1e3), 3),
                 "pct": round(100 * v / tot, 1),
                 "events_per_step": cnt[n] // steps}
                for n, v in sorted(fam.items(), key=lambda x: -x[1])[:14]],
        }
    except Exception as e:  # parsing is best-effort; ablations are primary
        return {"error": f"{type(e).__name__}: {e}"}


def main():
    base = dict(strategy="dp", dtype="bfloat16", ema_decay=0.99,
                log_every=10 ** 9, init_from="output/pretrained.msgpack",
                init_head=True)
    trace_dir = os.path.join(REPO, "results", "xprof_r05")
    off = {"PDNLP_FUSE_QKV": "0"}
    variants = {
        "base_split_qkv": (base, off),
        "fused_qkv": (base, {"PDNLP_FUSE_QKV": "1"}),
        "no_ema": ({**base, "ema_decay": 0.0}, off),
        "no_dropout": ({**base, "dropout": 0.0, "attn_dropout": 0.0}, off),
        "no_ema_no_dropout": (
            {**base, "ema_decay": 0.0, "dropout": 0.0, "attn_dropout": 0.0},
            off),
        "fp32": ({**base, "dtype": "float32"}, off),
        "bf16_grads_direct": ({**base, "grads_dtype": "compute"}, off),
        "bf16_grads_unroll1": (
            {**base, "grads_dtype": "compute", "scan_unroll": 1}, off),
        "b64": ({**base, "train_batch_size": 64}, off),
        "b128": ({**base, "train_batch_size": 128}, off),
        # tanh-GELU A/B (PDNLP_GELU_TANH): prices the exact-erf backward the
        # trace attributes ~3.3 ms/step to; a different model, so measured
        # here rather than shipped (models/bert.py:_gelu)
        "gelu_tanh": (base, {**off, "PDNLP_GELU_TANH": "1"}),
        "gelu_tanh_b64": ({**base, "train_batch_size": 64},
                          {**off, "PDNLP_GELU_TANH": "1"}),
        "gelu_tanh_b128": ({**base, "train_batch_size": 128},
                           {**off, "PDNLP_GELU_TANH": "1"}),
    }
    if len(sys.argv) > 1:
        if len(sys.argv) != 3 or sys.argv[1] != "--only":
            sys.exit(f"usage: {sys.argv[0]} [--only name,name,...]  "
                     f"(variants: {', '.join(variants)})")
        only = set(sys.argv[2].split(","))
        unknown = only - set(variants)
        if unknown:
            sys.exit(f"unknown variant(s): {', '.join(sorted(unknown))}  "
                     f"(variants: {', '.join(variants)})")
        variants = {k: v for k, v in variants.items() if k in only}
    # merge onto any existing artifact: reruns refresh rows, never drop the
    # rows (and analysis) other files cite as evidence
    path = os.path.join(REPO, "results", "profile_r05.json")
    results = {}
    prior = {}
    if os.path.exists(path):
        prior = json.load(open(path))
        results.update(prior.get("variants", {}))
    for name, (kw, env) in variants.items():
        td = trace_dir if name == "base_split_qkv" else None
        r = probe(kw, env=env, trace_dir=td)
        if r is not None:  # a failed probe must not null out a measured
            results[name] = r  # row the README/analysis cite (merge invariant)
        print(f"{name}: {r}", file=sys.stderr)

    out = dict(prior)
    out.update({
        "device": None,
        "config": "bert-base b32 s128 bf16 (bench recipe, fuse_steps=1 probe)",
        "variants": results,
    })
    if "base_split_qkv" in variants:  # trace only re-captured on a full run
        out["trace"] = parse_trace(trace_dir)
    try:
        import jax  # only now: every probe child has exited with the chip

        out["device"] = jax.devices()[0].device_kind
    except Exception:
        pass
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: v for k, v in results.items()}, indent=2))
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
