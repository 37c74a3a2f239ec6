"""Operations and bytes the ALGORITHM of the latent-attention, sparse-expert
decoder under a LEARNED SPARSE selection needs, from shapes alone
(``sizes``: the configuration file's numbers and its ``indexer_types``).  The
numerators of ``glm_*_roofline_pct`` and of ``glm_index_bytes_share_pct``;
they live with the benchmark so that no later PR can change them.  Padding
the program chooses to move (a cache row held wider than its values, a
prompt padded to its bucket, rows that are not live, picks that are not
real) does not count.

What a step must do: every held weight it touches read once — a held expert
only if a token chose it —, and per row the index keys of its whole context
in the ``full`` layers (the indexer scores every visible position), but only
``min(index_topk, context)`` latents in each of the layers: the selection's
point.  The index scores cost ``heads x dim x 2`` a visible position a
``full`` layer; the two attention dots run over the PICKED positions alone.
"""
from __future__ import annotations


def parts(sizes: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    H, N = sizes["hidden_size"], sizes["num_attention_heads"]
    qr, kr = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    Hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
    F = sizes["moe_intermediate_size"]
    width = int(sizes.get("router_width", sizes["n_routed_experts"]))
    return {
        "attention": H * qr + qr * N * (dn + dr) + H * (kr + dr)
        + kr * N * (dn + dv) + N * dv * H + 2 * H + qr + kr,
        "indexer": qr * Hi * di + H * di + 2 * di + H * Hi,
        "dense_ffn": 3 * H * sizes["intermediate_size"],
        "expert": 3 * H * F,
        "shared": 3 * H * F * int(sizes.get("n_shared_experts", 1)),
        "router": H * width + width,          # with the selection bias
        "embedding": sizes["vocab_size"] * H,
        "head": sizes["vocab_size"] * H,
    }


def layers(sizes: dict):
    """(dense layers, expert layers, ``full`` layers of either kind)."""
    dense = int(sizes.get("first_k_dense_replace", 1))
    held = list(sizes["indexer_types"])[:int(sizes["num_hidden_layers"])]
    return (dense, int(sizes["num_hidden_layers"]) - dense,
            sum(1 for kind in held if kind == "full"))


def params_held(sizes: dict) -> int:
    p, (dense, moe, full) = parts(sizes), layers(sizes)
    return (dense * (p["attention"] + p["dense_ffn"])
            + moe * (p["attention"] + p["router"] + p["shared"]
                     + sizes["n_routed_experts"] * p["expert"])
            + full * p["indexer"]
            + p["embedding"] + p["head"] + sizes["hidden_size"])


def latent_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """One latent vector ``[c_kv | k_rope]`` a layer."""
    return (int(sizes["num_hidden_layers"])
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) * itemsize)


def index_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """One index key a ``full`` layer."""
    return layers(sizes)[2] * sizes["index_head_dim"] * itemsize


def cache_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    return (latent_bytes_per_token(sizes, itemsize)
            + index_bytes_per_token(sizes, itemsize))


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


def picked_pairs(sizes: dict, tokens: float) -> float:
    """(query, picked position) pairs of a prompt of ``tokens`` from
    position 0: ``sum_t min(index_topk, t + 1)``."""
    k = min(float(sizes["index_topk"]), tokens)
    return k * (k + 1) / 2 + (tokens - k) * k


def _matrix_flops_per_token(sizes: dict, assignments_per_token: float) -> float:
    """Multiply-adds x 2 of one token through every layer's matrices, the
    indexer's among them; the products over cached positions left out.
    (Absorbed, ``W_kvb`` is applied to the query and to the output once a
    token: the same multiply-adds as expanding one position.)"""
    p, (dense, moe, full) = parts(sizes), layers(sizes)
    ffn = dense * p["dense_ffn"] + moe * (
        p["router"] + p["shared"] + assignments_per_token * p["expert"])
    return 2.0 * ((dense + moe) * p["attention"] + full * p["indexer"] + ffn)


def _bound(byts: float, flops: float, peak: dict, **more) -> dict:
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops, **more}


def decode_step_min_seconds(sizes: dict, rows: float, live_tokens: float,
                            peak: dict, assignments: float = None,
                            picked: float = None, itemsize: int = 2) -> dict:
    """The least time one decode step over ``rows`` streams can take: the
    weights it touches read once (the embedding: ``rows`` rows; a held
    expert only if a token chose it), every row's index keys read in the
    ``full`` layers, its PICKED latents read in every layer, the new latents
    and index keys written — over HBM bytes/s; or the step's FLOPs over the
    bf16 peak (index scores ``heads x dim x 2`` a live position a ``full``
    layer; the absorbed attention ``heads x (latent + kv_lora_rank) x 2`` a
    picked position a layer); whichever is larger.  ``live_tokens``: cached
    positions the live streams see, summed; ``picked``: positions picked,
    summed (default: ``min(index_topk, mean context)`` a row);
    ``assignments``: to held experts a layer (default: even routing).
    ``index_bytes``: the index keys' part of ``bytes``."""
    H, V, L = sizes["hidden_size"], sizes["vocab_size"], sizes["num_hidden_layers"]
    N, kr, dr = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
                 sizes["qk_rope_head_dim"])
    p, (_, moe, full) = parts(sizes), layers(sizes)
    if assignments is None:
        assignments = expected_assignments(sizes, rows)
    if picked is None:
        picked = rows * min(float(sizes["index_topk"]),
                            live_tokens / max(rows, 1))
    untouched = (sizes["n_routed_experts"]
                 - experts_touched(sizes, assignments)) * p["expert"]
    w_bytes = (params_held(sizes) - moe * untouched - V * H + rows * H) \
        * itemsize
    index = live_tokens * index_bytes_per_token(sizes, itemsize)
    byts = w_bytes + index + picked * latent_bytes_per_token(sizes, itemsize) \
        + rows * cache_bytes_per_token(sizes, itemsize)
    flops = rows * (_matrix_flops_per_token(sizes, assignments / max(rows, 1))
                    + 2.0 * H * V) \
        + live_tokens * full * sizes["index_n_heads"] \
        * sizes["index_head_dim"] * 2.0 \
        + picked * L * N * ((kr + dr) + kr) * 2.0
    return _bound(byts, flops, peak, index_bytes=index)


def prefill_min_seconds(sizes: dict, tokens: float, peak: dict,
                        assignments: float = None, itemsize: int = 2) -> dict:
    """The least time one prompt of ``tokens`` real tokens can take: every
    token through the matrices, ``tokens^2 / 2`` index scores a ``full``
    layer, causal attention over the expanded keys and values of the PICKED
    pairs (:func:`picked_pairs` x heads x (d_nope + d_rope + d_v) x 2 a
    layer), the head once; or the weights read once and the latents and
    index keys written; whichever is larger."""
    H, V, L = sizes["hidden_size"], sizes["vocab_size"], sizes["num_hidden_layers"]
    N = sizes["num_attention_heads"]
    d = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] + sizes["v_head_dim"]
    full = layers(sizes)[2]
    if assignments is None:
        assignments = expected_assignments(sizes, tokens)
    flops = tokens * _matrix_flops_per_token(
        sizes, assignments / max(tokens, 1)) \
        + 0.5 * tokens * tokens * full * sizes["index_n_heads"] \
        * sizes["index_head_dim"] * 2.0 \
        + picked_pairs(sizes, tokens) * L * N * d * 2.0 + 2.0 * H * V
    byts = (params_held(sizes) - V * H + tokens * H) * itemsize \
        + tokens * cache_bytes_per_token(sizes, itemsize)
    return _bound(byts, flops, peak)
