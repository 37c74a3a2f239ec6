"""Operations and bytes the ALGORITHM needs, from shapes alone.

These are the numerators of every ``*_mfu_pct`` and ``*_roofline_pct``; they
live with the benchmark so that no later PR can change them.  Recomputed
operations (remat) and padding the program chooses to move do not count.
"""
from __future__ import annotations


def encoder_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward matmul FLOPs per token SLOT of one pass through the encoder
    stack: QKV+O projections 4*H*H, MLP 2*H*I, attention scores and values
    2*S*H, times 2 FLOPs per multiply-add, per layer."""
    H, I, L = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["num_hidden_layers"])
    return L * 2.0 * (4 * H * H + 2 * H * I + 2 * seq_len * H)


def train_step_flops(sizes: dict, rows: int, seq_len: int) -> float:
    """Forward + backward (2x forward) of the classifier over ``rows`` rows
    of ``seq_len`` slots; pooler and head are counted, embeddings are
    lookups.  (The arithmetic of ``bench.py``'s ``step_flops``.)"""
    H, C = sizes["hidden_size"], sizes.get("num_labels", 2)
    fwd = rows * seq_len * encoder_flops_per_token(sizes, seq_len)
    fwd += rows * 2.0 * (H * H + H * C)
    return 3.0 * fwd


def param_count(sizes: dict, head: bool = True) -> int:
    """Parameters the decode step reads: the trunk's layers and embeddings,
    plus the LM head's transform (its decoder is the word table, tied)."""
    H, I, L = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["num_hidden_layers"])
    V, P, T = (sizes["vocab_size"], sizes["max_position_embeddings"],
               sizes["type_vocab_size"])
    layer = 4 * (H * H + H) + (H * I + I) + (I * H + H) + 4 * H
    n = L * layer + (V + P + T) * H + 2 * H
    if head:
        n += H * H + H + 2 * H + V
    return n


def kv_bytes_per_token(sizes: dict, kv_itemsize: int = 2) -> int:
    """K and V of one position over every layer."""
    return 2 * sizes["num_hidden_layers"] * sizes["hidden_size"] * kv_itemsize


def decode_step_min_seconds(sizes: dict, rows: int, live_tokens: float,
                            peak: dict, weight_itemsize: int = 2,
                            kv_itemsize: int = 2) -> dict:
    """The least time one decode step over ``rows`` streams can take on a
    chip with ``peak``: weights read once (the word table once, as the tied
    decoder; input lookups read ``rows`` rows), the live K/V read once, the
    new K/V written once — over HBM bytes/s; or the step's FLOPs over the
    bf16 peak; whichever is larger.  ``live_tokens`` is the number of cached
    positions the live streams attend to, summed."""
    H, L, V = sizes["hidden_size"], sizes["num_hidden_layers"], sizes["vocab_size"]
    w_bytes = param_count(sizes) * weight_itemsize
    kv_read = live_tokens * kv_bytes_per_token(sizes, kv_itemsize)
    kv_write = rows * kv_bytes_per_token(sizes, kv_itemsize)
    byts = w_bytes + kv_read + kv_write
    flops = rows * (encoder_flops_per_token(sizes, 0) + 2.0 * (H * H + H * V)) \
        + 2.0 * 2.0 * H * live_tokens * L
    t_b = byts / peak["hbm_bytes_per_s"]
    t_f = flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops}
