"""Per-channel symmetric int8 weight quantization for the serve forward.

Serving BERT-base at small batch is weight-bound: every forward streams
~220 MB of bf16 matmul kernels out of HBM while the MXU sits mostly idle.
Storing those kernels as int8 (+ one fp32 scale per output channel) halves
the weight traffic — the throughput lever ``--serve_dtype int8`` pulls —
while activations stay bf16 and the scale multiply folds onto the matmul
OUTPUT (per-column scales commute through the contraction:
``x @ (q * s) == (x @ q) * s``), so no dequantized weight copy ever
materializes.

Scope (the exact ``train.steps.cast_kernels`` rule, restricted to dense
blocks): every ``{"kernel", "bias"}`` dict whose kernel has >= 2 dims —
q/k/v/o, the MLP up/down (incl. the stacked ``[L, ...]`` and MoE
``[L, E, ...]`` layouts), pooler, classifier.  Embeddings (gathers, not
matmuls), LayerNorms, biases, and the bias-less MoE gate (a [H, E] sliver
whose routing is precision-sensitive) stay fp32.

Calibration is weight-only (symmetric max per output channel) — no
activation statistics needed, so ``scripts/quantize_ckpt.py`` can produce
the artifact offline from any committed checkpoint.  Accuracy parity is
pinned in ``tests/test_kernels.py``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

#: marker key: a dense dict carrying one is quantized ({kernel: int8,
#: qscale: fp32 per-output-channel, bias: fp32})
QSCALE = "qscale"


def _is_dense(node: Any) -> bool:
    return (isinstance(node, dict) and "kernel" in node and "bias" in node
            and getattr(node["kernel"], "ndim", 0) >= 2)


def quantize_dense(kernel, bias) -> Dict[str, Any]:
    """One dense block -> {kernel int8, qscale fp32, bias} (host numpy).

    Per-OUTPUT-channel symmetric scales: amax over the contraction (input)
    dim, ``axis=-2`` — stacked layouts ([L, in, out], [L, E, in, out]) get
    one scale per (stack..., out) automatically."""
    w = np.asarray(kernel, np.float32)
    amax = np.abs(w).max(axis=-2)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale[..., None, :]), -127, 127).astype(np.int8)
    return {"kernel": q, QSCALE: scale,
            "bias": np.asarray(bias, np.float32)}


def quantize_params(params) -> Dict[str, Any]:
    """Quantize every eligible dense block of a (host or device) param
    tree; everything else passes through as host numpy."""

    def walk(node):
        if _is_dense(node) and QSCALE not in node:
            return quantize_dense(node["kernel"], node["bias"])
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return np.asarray(node)

    return walk(params)


def dequantize_dense(node: Dict[str, Any]) -> np.ndarray:
    """int8 kernel -> fp32 approximation (error reporting / tests)."""
    return (np.asarray(node["kernel"], np.float32)
            * np.asarray(node[QSCALE], np.float32)[..., None, :])


def is_quantized(tree: Any) -> bool:
    """True when any dense block in the tree carries a ``qscale`` — how the
    engine recognizes an offline ``quantize_ckpt.py`` artifact."""
    if isinstance(tree, dict):
        return QSCALE in tree or any(is_quantized(v) for v in tree.values())
    return False


def quant_error_report(params, qparams) -> Dict[str, Tuple[float, float]]:
    """{path: (max_abs_err, rel_err)} per quantized block — the
    ``quantize_ckpt.py`` summary."""
    out: Dict[str, Tuple[float, float]] = {}

    def walk(node, qnode, path):
        if _is_dense(node) and isinstance(qnode, dict) and QSCALE in qnode:
            w = np.asarray(node["kernel"], np.float32)
            dq = dequantize_dense(qnode)
            err = float(np.abs(w - dq).max())
            denom = float(np.abs(w).max()) or 1.0
            out[path or "<root>"] = (err, err / denom)
        elif isinstance(node, dict):
            for k in node:
                walk(node[k], qnode.get(k) if isinstance(qnode, dict) else None,
                     f"{path}/{k}" if path else k)

    walk(params, qparams, "")
    return out
