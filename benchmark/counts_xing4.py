"""Operations and bytes the ALGORITHM of the latent-attention, sparse-expert
decoder with a FOUR-STREAM residual needs, from shapes alone (``sizes``: the
configuration file's numbers).  The numerators of ``xing_*_roofline_pct``
and of ``xing_mhc_bytes_share_pct``; they live with the benchmark so that no
later PR can change them.

The trunk is ``counts_axk1``'s (MLA, the dense layer, the router, the
shared and the held experts, the head: every held expert's weights read
once a decode step, the whole head) — ``n_routed_experts`` counts the
experts HELD, here all of them.  What this family adds, a SUB-LAYER (two a
layer), over ``n = hc_mult`` streams of ``C = hidden_size``:

- leaves: ``phi [n*C, n*(n+2)]``, ``b [n*(n+2)]``, ``a [3]``; a layer with
  experts also holds the router's selection bias ``[experts]``;
- bytes a token: the streams read once and written once (``2 n C`` values);
  what the sub-layer itself reads (``u``) and writes (``y``) is the trunk's;
- operations a token: the norm statistic ``2 n C``, the ``phi`` products
  ``2 n C n (n+2)``, ``H_pre @ X`` ``2 n C``, ``H_res @ X + outer`` ``2 n (n+1)
  C``, and ``hc_sinkhorn_iters`` x 2 normalisations of ``n x n`` (a sum and
  a division an entry).
"""
from __future__ import annotations

from benchmark import counts_axk1 as trunk

layers = trunk.layers
cache_bytes_per_token = trunk.cache_bytes_per_token
expected_assignments = trunk.expected_assignments


def mixing_params(sizes: dict) -> int:
    """One sub-layer's mixing leaves."""
    n, C = int(sizes["hc_mult"]), sizes["hidden_size"]
    k = n * (n + 2)
    return n * C * k + k + 3


def parts(sizes: dict) -> dict:
    """Parameters by part, as this chip holds them."""
    return {**trunk.parts(sizes), "mixing": 2 * mixing_params(sizes),
            "router_bias": int(sizes["n_routed_experts"])}


def added_params(sizes: dict) -> int:
    """What the mixing and the selection bias add to the trunk's count."""
    p, (dense, moe) = parts(sizes), layers(sizes)
    return (dense + moe) * p["mixing"] + moe * p["router_bias"]


def params_held(sizes: dict) -> int:
    return trunk.params_held(sizes) + added_params(sizes)


def sublayers(sizes: dict) -> int:
    return 2 * int(sizes["num_hidden_layers"])


def stream_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """The ``n`` streams of one token: what a layer carries to the next."""
    return int(sizes["hc_mult"]) * sizes["hidden_size"] * itemsize


def mixing_bytes_per_token(sizes: dict, itemsize: int = 2) -> int:
    """Every sub-layer reads the streams once and writes them once."""
    return sublayers(sizes) * 2 * stream_bytes_per_token(sizes, itemsize)


def mixing_flops_per_token(sizes: dict) -> float:
    n, C = int(sizes["hc_mult"]), sizes["hidden_size"]
    one = (2.0 * n * C                          # the norm's statistic
           + 2.0 * n * C * n * (n + 2)          # the phi products
           + 2.0 * n * C                        # H_pre @ X
           + 2.0 * n * (n + 1) * C              # H_res @ X + outer(H_post, y)
           + int(sizes["hc_sinkhorn_iters"]) * 2 * 2.0 * n * n)
    return sublayers(sizes) * one


def _with_mixing(base: dict, sizes: dict, tokens: float, peak: dict,
                 itemsize: int) -> dict:
    mixing = added_params(sizes) * itemsize \
        + tokens * mixing_bytes_per_token(sizes, itemsize)
    byts = base["bytes"] + mixing
    flops = base["flops"] + tokens * mixing_flops_per_token(sizes)
    t_b, t_f = byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"]
    return {"seconds": max(t_b, t_f), "bound": "bytes" if t_b >= t_f else "flops",
            "bytes": byts, "flops": flops, "mixing_bytes": mixing}


def decode_step_min_seconds(sizes: dict, rows: float, live_tokens: float,
                            peak: dict, assignments: float = None,
                            itemsize: int = 2) -> dict:
    """The least time one decode step over ``rows`` streams can take:
    ``counts_axk1``'s (weights read once, the live latents read once, the new
    ones written) plus the mixing's leaves read once and every row's streams
    read and written once a sub-layer — over HBM bytes/s; or the step's
    FLOPs, the mixing's among them, over the bf16 peak; whichever is larger.
    ``mixing_bytes``: the mixing's part of ``bytes``."""
    base = trunk.decode_step_min_seconds(sizes, rows, live_tokens, peak,
                                         assignments, itemsize)
    return _with_mixing(base, sizes, rows, peak, itemsize)


def prefill_min_seconds(sizes: dict, tokens: float, peak: dict,
                        assignments: float = None, itemsize: int = 2) -> dict:
    """The least time one prompt of ``tokens`` real tokens can take:
    ``counts_axk1``'s plus the mixing of every token."""
    base = trunk.prefill_min_seconds(sizes, tokens, peak, assignments,
                                     itemsize)
    return _with_mixing(base, sizes, tokens, peak, itemsize)
