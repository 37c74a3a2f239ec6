"""Operations and bytes the ALGORITHM of the latent-attention, sparse-expert
decoder needs, from shapes alone (``sizes``: the configuration file's
numbers).  The numerators of this family's ``*_roofline_pct``; they live with
the benchmark so that no later PR can change them.  Padding the program
chooses to move (a cache row held wider than its values, a prompt padded to
its bucket, rows that are not live) does not count.
"""
from __future__ import annotations


def parts(sizes: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    H, N = sizes["hidden_size"], sizes["num_attention_heads"]
    qr, kr = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    F = sizes["moe_intermediate_size"]
    return {
        "attention": H * qr + qr * N * (dn + dr) + H * (kr + dr)
        + kr * N * (dn + dv) + N * dv * H + 2 * H + qr + kr,
        "dense_ffn": 3 * H * sizes["intermediate_size"],
        "expert": 3 * H * F,
        "shared": 3 * H * F * int(sizes.get("n_shared_experts", 1)),
        "router": H * int(sizes.get("router_width", sizes["n_routed_experts"])),
        "embedding": sizes["vocab_size"] * H,
        "head": sizes["vocab_size"] * H,
    }


def layers(sizes: dict):
    """(dense layers, expert layers)."""
    dense = int(sizes.get("first_k_dense_replace", 1))
    return dense, int(sizes["num_hidden_layers"]) - dense


def params_held(sizes: dict) -> int:
    p, (dense, moe) = parts(sizes), layers(sizes)
    return (dense * (p["attention"] + p["dense_ffn"])
            + moe * (p["attention"] + p["router"] + p["shared"]
                     + sizes["n_routed_experts"] * p["expert"])
            + p["embedding"] + p["head"] + sizes["hidden_size"])


def cache_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """One latent vector ``[c_kv | k_rope]`` a layer."""
    return (int(sizes["num_hidden_layers"])
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) * itemsize)


def expected_assignments(sizes: dict, tokens: float) -> float:
    """Assignments to held experts a layer, under even routing."""
    width = int(sizes.get("router_width", sizes["n_routed_experts"]))
    return tokens * sizes["num_experts_per_tok"] \
        * sizes["n_routed_experts"] / width


def _linear_flops_per_token(sizes: dict, assignments_per_token: float) -> float:
    """Multiply-adds x 2 of one token through every layer's matrices, the
    attention's products over cached positions left out.  (Absorbed,
    ``W_kvb`` is applied to the query and to the output once a token: the
    same multiply-adds as expanding one position.)"""
    p, (dense, moe) = parts(sizes), layers(sizes)
    ffn = dense * p["dense_ffn"] + moe * (
        p["router"] + p["shared"] + assignments_per_token * p["expert"])
    return 2.0 * ((dense + moe) * p["attention"] + ffn)


def decode_step_min_seconds(sizes: dict, rows: float, live_tokens: float,
                            peak: dict, assignments: float = None,
                            itemsize: int = 2) -> dict:
    """The least time one decode step over ``rows`` streams can take:
    weights read once (the embedding: ``rows`` rows), the live latents read
    once, the new latents written once — over HBM bytes/s; or the step's
    FLOPs over the bf16 peak, the absorbed attention costing ``heads x
    (latent + kv_lora_rank) x 2`` a cached position a layer; whichever is
    larger.  ``live_tokens``: cached positions the live streams attend to,
    summed; ``assignments``: to held experts a layer (default: even routing)."""
    H, V, L = sizes["hidden_size"], sizes["vocab_size"], sizes["num_hidden_layers"]
    N, kr, dr = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
                 sizes["qk_rope_head_dim"])
    if assignments is None:
        assignments = expected_assignments(sizes, rows)
    w_bytes = (params_held(sizes) - sizes["vocab_size"] * H + rows * H) * itemsize
    kv = cache_bytes_per_token(sizes, itemsize)
    byts = w_bytes + live_tokens * kv + rows * kv
    flops = rows * (_linear_flops_per_token(sizes, assignments / max(rows, 1))
                    + 2.0 * H * V) \
        + live_tokens * L * N * ((kr + dr) + kr) * 2.0
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops}


def prefill_min_seconds(sizes: dict, tokens: float, peak: dict,
                        assignments: float = None, itemsize: int = 2) -> dict:
    """The least time one prompt of ``tokens`` real tokens can take: every
    token through the matrices, causal attention over the expanded keys and
    values (``tokens^2 / 2`` pairs x heads x (d_nope + d_rope + d_v) x 2 a
    layer), the head once; or the weights read once and the latents
    written; whichever is larger."""
    H, V, L = sizes["hidden_size"], sizes["vocab_size"], sizes["num_hidden_layers"]
    N = sizes["num_attention_heads"]
    d = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] + sizes["v_head_dim"]
    if assignments is None:
        assignments = expected_assignments(sizes, tokens)
    flops = tokens * _linear_flops_per_token(
        sizes, assignments / max(tokens, 1)) \
        + 0.5 * tokens * tokens * L * N * d * 2.0 + 2.0 * H * V
    byts = (params_held(sizes) - sizes["vocab_size"] * H + tokens * H) * itemsize \
        + tokens * cache_bytes_per_token(sizes, itemsize)
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops}
