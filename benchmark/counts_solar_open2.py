"""Operations and bytes the ALGORITHM of the hybrid decoder (gated delta-rule
linear attention beside softmax GQA, sparse experts in every layer) needs,
from shapes alone (``sizes``: the configuration file's numbers, its
``linear_attn_config`` group and its ``gqa_layers`` list).  The numerators of
this family's ``*_roofline_pct``; they live with the benchmark so that no
later PR can change them.  Padding the program chooses to move (a prompt
padded to its bucket, cache pages past a stream's end, rows that are not
live) does not count, and an expert no token chose is not read.
"""
from __future__ import annotations

CHUNK = 64      # positions a chunk of the chunkwise delta rule (its FLOPs)


def linear_dims(sizes: dict):
    """(heads, head size, convolution width, low rank) of a linear layer."""
    la = sizes["linear_attn_config"]
    return (int(la["num_heads"]), int(la["head_dim"]),
            int(la["short_conv_kernel_size"]), int(sizes["kda_low_rank"]))


def layers(sizes: dict):
    """(GQA layers, linear layers) of the depth that is run."""
    L = int(sizes["num_hidden_layers"])
    gqa = sum(1 for l in sizes["gqa_layers"] if l < L)
    return gqa, L - gqa


def parts(sizes: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    H, F = sizes["hidden_size"], sizes["moe_intermediate_size"]
    N = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    n, d, K, r = linear_dims(sizes)
    W = n * d
    return {
        "gqa": 3 * H * N + 2 * H * kv + 2 * H,
        "linear": 4 * H * W + 2 * (H * r + r * W) + H * n + 3 * K * W
        + n + W + d + 2 * H,
        "expert": 3 * H * F,
        "shared": 3 * H * F * int(sizes.get("n_shared_experts", 1)),
        "router": H * int(sizes.get("router_width", sizes["n_routed_experts"])),
        "embedding": sizes["vocab_size"] * H,
        "head": sizes["vocab_size"] * H,
    }


def params_held(sizes: dict) -> int:
    p, (gqa, lin) = parts(sizes), layers(sizes)
    return (gqa * p["gqa"] + lin * p["linear"]
            + (gqa + lin) * (p["router"] + p["shared"]
                             + sizes["n_routed_experts"] * p["expert"])
            + p["embedding"] + p["head"] + sizes["hidden_size"])


def cache_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """K and V of the GQA layers: the only layers a position is cached in."""
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    return layers(sizes)[0] * 2 * kv * itemsize


def state_bytes_per_slot(sizes: dict, itemsize: int = 2) -> int:
    """What one stream's linear layers keep, whatever its length: a float32
    state ``d x d`` a head and the convolution's last ``K - 1`` inputs."""
    n, d, K, _ = linear_dims(sizes)
    return layers(sizes)[1] * (n * d * d * 4 + (K - 1) * 3 * n * d * itemsize)


def expected_assignments(sizes: dict, tokens: float) -> float:
    """Assignments to held experts a layer, under even routing."""
    width = int(sizes.get("router_width", sizes["n_routed_experts"]))
    return tokens * sizes["num_experts_per_tok"] \
        * sizes["n_routed_experts"] / width


def experts_touched(sizes: dict, assignments: float) -> float:
    """Held experts at least one of ``assignments`` (a layer) fell on, under
    even routing among them: the experts whose weights must be read."""
    held = sizes["n_routed_experts"]
    return held * (1.0 - (1.0 - 1.0 / held) ** assignments)


def _matrix_flops_per_token(sizes: dict, assignments_per_token: float) -> float:
    """Multiply-adds x 2 of one token through every layer's matrices (the
    products over cached positions and over the state left out)."""
    p, (gqa, lin) = parts(sizes), layers(sizes)
    ffn = (gqa + lin) * (p["router"] + p["shared"]
                         + assignments_per_token * p["expert"])
    return 2.0 * (gqa * p["gqa"] + lin * p["linear"] + ffn)


def decode_step_min_seconds(sizes: dict, rows: float, live_tokens: float,
                            peak: dict, assignments: float = None,
                            itemsize: int = 2) -> dict:
    """The least time one decode step over ``rows`` streams can take: the
    weights it touches read once (the embedding: ``rows`` rows; the experts:
    those a token chose), every row's state and convolution tail read AND
    written, the live keys and values read once, the new ones written — over
    HBM bytes/s; or the step's FLOPs over the bf16 peak (attention ``heads x
    2 d x 2`` a cached position a GQA layer, the recurrence about ``6 d^2`` a
    head a linear layer); whichever is larger.  ``live_tokens``: cached
    positions the live streams attend to, summed; ``assignments``: to held
    experts a layer (default: even routing)."""
    H, V = sizes["hidden_size"], sizes["vocab_size"]
    p, (gqa, lin) = parts(sizes), layers(sizes)
    n, d, _, _ = linear_dims(sizes)
    if assignments is None:
        assignments = expected_assignments(sizes, rows)
    untouched = (sizes["n_routed_experts"]
                 - experts_touched(sizes, assignments)) * p["expert"]
    w_bytes = (params_held(sizes) - (gqa + lin) * untouched
               - sizes["vocab_size"] * H + rows * H) * itemsize
    kv = cache_bytes_per_token(sizes, itemsize)
    state = 2.0 * rows * state_bytes_per_slot(sizes, itemsize)
    byts = w_bytes + state + live_tokens * kv + rows * kv
    flops = rows * (_matrix_flops_per_token(sizes, assignments / max(rows, 1))
                    + 2.0 * H * V + lin * n * 6.0 * d * d) \
        + live_tokens * gqa * sizes["num_attention_heads"] \
        * 2 * sizes["head_dim"] * 2.0
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops, "state_bytes": state}


def prefill_min_seconds(sizes: dict, tokens: float, peak: dict,
                        assignments: float = None, itemsize: int = 2) -> dict:
    """The least time one prompt of ``tokens`` real tokens can take: every
    token through the matrices, causal softmax attention in the GQA layers
    (``tokens^2 / 2`` pairs x heads x 2 d x 2), the chunkwise delta rule in
    the linear layers (a token a head: the two intra-chunk score sums, the
    triangular solve and the scores' product over half a chunk, three ``d x
    d`` products with the state), the head once; or the weights read once
    and the cache and the final state written; whichever is larger."""
    H, V = sizes["hidden_size"], sizes["vocab_size"]
    gqa, lin = layers(sizes)
    n, d, _, _ = linear_dims(sizes)
    if assignments is None:
        assignments = expected_assignments(sizes, tokens)
    chunk = 2.0 * (4 * (CHUNK / 2) * d + 3 * d * d)
    flops = tokens * (_matrix_flops_per_token(
        sizes, assignments / max(tokens, 1)) + lin * n * chunk) \
        + 0.5 * tokens * tokens * gqa * sizes["num_attention_heads"] \
        * 2 * sizes["head_dim"] * 2.0 + 2.0 * H * V
    byts = (params_held(sizes) - sizes["vocab_size"] * H + tokens * H) * itemsize \
        + tokens * cache_bytes_per_token(sizes, itemsize) \
        + state_bytes_per_slot(sizes, itemsize)
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops}
