#!/usr/bin/env python
"""Offline int8 weight quantization of a committed checkpoint.

Produces the serving artifact ``--serve_dtype int8`` can load directly:
every dense block's kernel stored as per-channel symmetric int8 + one fp32
scale per output channel (``pdnlp_tpu.serve.quant`` — the identical math
the engine applies when quantizing a float checkpoint on the fly, so the
two routes can never disagree).  Calibration is weight-only: no data, no
device — this runs anywhere the checkpoint file does.

    python scripts/quantize_ckpt.py output/dp-cls.msgpack
    # -> output/dp-cls.int8.msgpack + a per-block error report

    python serve_tpu.py --serve_dtype int8 --ckpt output/dp-cls.int8.msgpack

``--kv_calib MODEL`` additionally emits the int8 KV-cache scale tables the
generative decode engine consumes (``--kv_dtype int8``): per-(layer, head,
channel) symmetric scales from the SEEDED synthetic causal forward in
``pdnlp_tpu.models.decoder.calibrate_kv_scales`` — the exact computation
the engine runs when self-calibrating at warmup, so the offline artifact
and the online fallback can never disagree.  The tables land beside the
INPUT checkpoint as ``<stem>.kvscales.msgpack`` through the same
crash-atomic manifest-verified publish, and ``PagedDecodeEngine`` auto-loads
them when the checkpoint swaps in.  The decoder's LM head is MLM-shaped
(its ``transform`` dense block): pointing this script at a saved head
artifact quantizes it through the identical per-channel path.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flax import serialization  # noqa: E402

from pdnlp_tpu.serve.quant import (  # noqa: E402
    is_quantized, quant_error_report, quantize_params,
)
from pdnlp_tpu.train import checkpoint as ckpt  # noqa: E402


def emit_kv_scales(params, model: str, checkpoint: str) -> str:
    """Calibrate + publish the int8 KV scale tables for ``checkpoint``
    (sidecar ``<stem>.kvscales.msgpack``, manifest-verified)."""
    import numpy as np

    from pdnlp_tpu.models import get_config
    from pdnlp_tpu.models.decoder import calibrate_kv_scales

    vocab = int(np.asarray(params["embeddings"]["word"]).shape[0])
    cfg = get_config(model, vocab_size=vocab)
    k_scale, v_scale = calibrate_kv_scales(params, cfg)
    out = checkpoint.rsplit(".msgpack", 1)[0] + ".kvscales.msgpack"
    ckpt.publish(out, serialization.to_bytes(
        {"k_scale": k_scale, "v_scale": v_scale}))
    print(f"wrote {out}  (KV scale tables {k_scale.shape}, model={model}, "
          f"vocab={vocab})")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("checkpoint", help="params checkpoint (.msgpack)")
    p.add_argument("-o", "--output", default=None,
                   help="artifact path (default: <checkpoint>.int8.msgpack)")
    p.add_argument("--kv_calib", default=None, metavar="MODEL",
                   help="also emit int8 KV-cache scale tables for this "
                        "registry model (generative decode, --kv_dtype "
                        "int8); runs a seeded synthetic causal forward — "
                        "no data, CPU is fine")
    ns = p.parse_args(argv)

    params = ckpt.load_raw(ns.checkpoint)
    if is_quantized(params):
        print(f"{ns.checkpoint} is already an int8 artifact", file=sys.stderr)
        return 1
    if ns.kv_calib:
        emit_kv_scales(params, ns.kv_calib, ns.checkpoint)
    qparams = quantize_params(params)
    report = quant_error_report(params, qparams)
    if not report:
        print(f"{ns.checkpoint}: no dense blocks found — not a params "
              "checkpoint?", file=sys.stderr)
        return 1

    out = ns.output or (ns.checkpoint.rsplit(".msgpack", 1)[0]
                        + ".int8.msgpack")
    # crash-atomic + checksum manifest, like every other published
    # checkpoint — a truncated artifact then fails loudly at load time
    # instead of three layers later as an opaque msgpack error
    ckpt.publish(out, serialization.to_bytes(qparams))

    in_bytes = os.path.getsize(ns.checkpoint)
    print(f"wrote {out}  ({in_bytes / 1e6:.1f} MB -> "
          f"{os.path.getsize(out) / 1e6:.1f} MB)")
    print(f"{'block':<28} {'max|dW|':>10} {'rel':>8}")
    for path, (err, rel) in sorted(report.items()):
        print(f"{path:<28} {err:>10.2e} {rel:>8.2%}")
    worst = max(rel for _, rel in report.values())
    print(f"worst per-block relative error: {worst:.2%} "
          "(symmetric per-channel int8 bound: <= 1/127 of the channel amax)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
