"""Causal decoder head over the BERT trunk + the KV-cache decode math.

The serving tier was classification-shaped: one forward, one logit row per
request.  Generative decoding inverts the cost structure — autoregressive
decode is memory-bandwidth-bound, so tokens/s is won on *not recomputing*
the prompt every token.  This module is the pure-math half of that story
(the serving half — slots, pages, continuous batching, budgets — lives in
``pdnlp_tpu.serve.decode``):

- **one trunk, three programs**: the decoder reuses the classifier's param
  tree (``bert.init_params`` — embeddings, stacked layers) under an LM
  head shaped exactly like the MLM head (``init_lm_head`` — transform +
  LayerNorm + decoder TIED to the word embeddings), so any strategy
  checkpoint serves generatively without conversion.  :func:`prefill`
  runs the prompt causally and RETURNS the per-layer K/V it computed;
  :func:`paged_decode_step` advances one token against the paged cache
  (one core, :func:`paged_attend_layers`, also behind the chunk and verify
  steps); :func:`infill_logits` is the bidirectional MLM-infilling scorer
  (same trunk, no causal mask — BERT's native objective served online).
- **KV cache layout** ``[L, n_pages, page_sz, H]``: the paged-cache note
  below says why the heads are folded and what every program promises.
- **the parity contract**: incremental decode over a live cache is
  bitwise equal, per step, to a FULL RECOMPUTE from a cold cache through
  the same programs — a fresh prefill of the prompt plus a from-scratch
  replay of every generated token, nothing reused
  (``tests/test_decode.py``).  That holds because every decode shape is
  FIXED (``[rows, 1]`` tokens, ``[rows]`` positions, the preallocated
  pools), so both sides run identical programs on bitwise-equal inputs,
  and the -1e9 additive masks zero invisible keys' probabilities EXACTLY
  (masked cache rows contribute exact ``+0.0`` regardless of their stale
  contents).  Against the one-shot WIDE causal forward — the tests'
  oracle — the comparison is argmax-exact within ~1e-6 instead: XLA's
  CPU gemm blocks the contraction differently per row extent (measured:
  ``[3, 512] @ [512, 128]`` vs the same rows at extent 96 differ by
  ULPs), so a ``[rows, 1]`` pass and a ``[rows, S]`` pass are only
  accumulation-order-equal, not bit-equal, on that backend.
- **int8 KV** (:func:`quantize_kv` / :func:`dequantize_kv`): the cache
  stores int8 against per-(layer, head, channel) symmetric scale tables —
  the PR-6 per-channel machinery pointed at activations.  Scales are
  CALIBRATED (:func:`calibrate_kv_scales` — a seeded synthetic forward,
  identical math offline in ``scripts/quantize_ckpt.py --kv_calib`` and
  online at engine warmup, so the two routes can never disagree); new K/V
  quantize on write, the pages read dequantize by one broadcast
  multiply, and no fp32 copy of the cache ever persists.

The hot decode shapes are fixed by construction — ``[rows, 1]`` tokens,
``[rows]`` positions, a table cut to a warmed rung, the preallocated pools
— so a jitted :func:`paged_decode_step` can never retrace after its first
trace per rung (the serve engine donates the pools across steps; jaxlint
R16 polices the rebuild-the-cache anti-pattern).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pdnlp_tpu.models import bert
from pdnlp_tpu.models.config import BertConfig
from pdnlp_tpu.ops import paged
from pdnlp_tpu.ops.attention import (NEG_INF, dot_product_attention,
                                     mask_bias, pin_auto_for_mesh)

Params = Dict[str, Any]

#: seeded synthetic calibration batch (shared by the offline artifact and
#: engine self-calibration — identical inputs => identical scale tables)
CALIB_SEED = 20240801
CALIB_ROWS = 4


def init_lm_head(key: jax.Array, cfg: BertConfig) -> Params:
    """LM head params — the MLM head's exact tree (transform + LayerNorm +
    per-token bias; decoder tied to the word embeddings), kept as a
    SEPARATE tree so classifier checkpoints load into the trunk unchanged.
    One init for both roles: MLM infilling and causal next-token share the
    head, which is what lets a single checkpoint serve both scorers."""
    return bert.init_mlm_head(key, cfg)


def lm_logits(params: Params, head: Params, cfg: BertConfig,
              hidden: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """[B, S, H] -> [B, S, vocab] fp32 (tied decoder — ``bert.mlm_logits``)."""
    return bert.mlm_logits(params, head, cfg, hidden, dtype=dtype)


# --------------------------------------------------------------- attention

def _qkv(x: jax.Array, lp: Params, cfg: BertConfig, dtype):
    B, S = x.shape[0], x.shape[1]
    N, D = cfg.num_heads, cfg.head_dim

    def heads(t):
        return t.reshape(B, S, N, D)

    return (heads(bert._dense(x, lp["q"], dtype)),
            heads(bert._dense(x, lp["k"], dtype)),
            heads(bert._dense(x, lp["v"], dtype)))


def _finish_layer(x, lp, cfg, attn, dtype):
    """Post-attention half of one trunk layer (deterministic serve form):
    output projection + residual LN + MLP + residual LN — ``bert``'s exact
    ops, so decoder hidden states match the trunk bit for bit."""
    B, S = x.shape[0], x.shape[1]
    attn = bert._dense(attn.reshape(B, S, -1), lp["o"], dtype)
    x = bert._layer_norm(x + attn, lp["attn_ln"]["scale"],
                         lp["attn_ln"]["bias"], cfg.layer_norm_eps)
    h = bert._gelu(bert._dense(x, lp["up"], dtype), cfg.gelu)
    h = bert._dense(h, lp["down"], dtype)
    return bert._layer_norm(x + h, lp["mlp_ln"]["scale"],
                            lp["mlp_ln"]["bias"], cfg.layer_norm_eps)


def _check_dense_trunk(layers: Params) -> None:
    if "gate" in layers:
        raise ValueError(
            "generative decoding over this trunk's capacity-routed experts "
            "is not supported (the dispatch drops overflow and has no "
            "cached single-token form); serve a dense checkpoint (--model "
            "without -moe), or a preset of the latent_moe family, whose "
            "expert layer is dropless (models/latent_moe.py)")


# ----------------------------------------------------------------- prefill

def run_layers_kv(layers: Params, cfg: BertConfig, x: jax.Array, *,
                  bias: jax.Array, causal: bool = True,
                  dtype=jnp.float32, unroll=True
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal layer scan that also RETURNS what it computed: hidden
    [B, S, H] plus per-layer K/V stacked ``[L, B, S, N, D]`` — the arrays
    the serve engine scatters into its pages, at zero extra compute
    (prefill had to build them anyway; the classifier path just threw
    them away).  Attention rides ``ops.attention`` (the causal
    composition and its routing live there, not here)."""
    _check_dense_trunk(layers)

    def layer(carry, scanned):
        x = carry
        lp, _ = scanned
        q, k, v = _qkv(x, lp, cfg, dtype)
        # "auto" routes causal/decode shapes to XLA everywhere today
        # (routed_impl: the flash kernel has no causal term) while leaving
        # the decision at the ops routing point, not pinned here
        attn = dot_product_attention(q, k, v, bias, impl="auto",
                                     causal=causal)
        return _finish_layer(x, lp, cfg, attn, dtype), (k, v)

    li = jnp.arange(cfg.num_layers)
    x, (ks, vs) = jax.lax.scan(layer, x, (layers, li), unroll=unroll)
    return x, ks, vs


def prefill(params: Params, head: Params, cfg: BertConfig,
            input_ids: jax.Array,       # [B, S] int32 (left-aligned)
            attention_mask: jax.Array,  # [B, S] {0,1}
            last_pos: jax.Array,        # [B] int32: index of last real token
            *, dtype=jnp.float32, unroll=True
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal prompt forward: next-token logits [B, vocab] (fp32, read at
    each row's ``last_pos``) + the per-layer K/V ``[L, B, S, N, D]``.

    The mask is causal ∘ key-padding (``ops.attention.causal_bias`` — the
    sanctioned quadratic site, composed inside ``dot_product_attention``):
    with left-aligned prompts the causal term already hides padding from
    every real row, and the explicit padding term keeps the composition
    correct for any caller that right-pads."""
    zeros = jnp.zeros_like(input_ids)
    x, _ = bert.embed(params, cfg, input_ids, zeros, dtype=dtype,
                      deterministic=True)
    bias = mask_bias(attention_mask, jnp.float32)
    hidden, ks, vs = run_layers_kv(params["layers"], cfg, x, bias=bias,
                                   causal=True, dtype=dtype, unroll=unroll)
    h_last = jnp.take_along_axis(
        hidden, last_pos.astype(jnp.int32)[:, None, None], axis=1)  # [B,1,H]
    logits = lm_logits(params, head, cfg, h_last, dtype=dtype)[:, 0]
    return logits, ks, vs


# ------------------------------------------------------------------ decode

def quantize_kv(x: jax.Array, scale: jax.Array) -> jax.Array:
    """fp K/V rows -> int8 against per-(head, channel) scales (broadcast
    over leading dims) — the PR-6 symmetric per-channel rule on
    activations."""
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """int8 cache slab -> compute dtype by one broadcast multiply (no fp32
    copy persists — the multiply fuses into the attention reads)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------- paged cache
#
# The paged layout stores K/V as fixed-size pages ``[L, n_pages, page_sz,
# H]`` (H = heads x head width, one position's K or V as ONE row) and a
# per-stream PAGE TABLE maps logical page -> physical page.  The heads are
# NOT an axis of the pool: the chip lays an array out by its two minor
# dimensions, and with ``[.., N, D]`` = ``[.., 12, 64]`` there the TPU's
# default layout makes the PAGE axis the minor one — every program that
# indexes pages then converts the whole pool to a padded row-major copy and
# back (read on the chip, PR 26: 80 of a decode step's 126 ms).  ``[page_sz,
# H]`` = ``[16, 768]`` tiles exactly, so a page is 24 KB contiguous.  The
# same compiler moves the whole pool again for a scatter whose window spans
# the LAYER axis, so every index below addresses major axes only.  The
# contract of every program below (the engine donates both pools to each):
#
# - **the pool is never rebuilt**: it is not sliced per layer, not scanned
#   over and not re-stacked.  Writes are ONE scatter per layer on the flat
#   view ``[L * P * page_sz, H]`` (a reshape of major dimensions: no data
#   moves) at ``layer * P * page_sz + physical_page * page_sz + offset``,
#   so the donated buffer IS the output buffer
#   (``tests/test_paged_core.py`` asserts the aliasing and that no
#   temporary is as large as a pool);
# - **reads are by page**: whole ``[page_sz, H]`` pages gathered through
#   the table from the view ``[L * P, page_sz, H]``, over the extent the
#   TABLE HANDED IN covers — the engine hands the decode step a table cut to
#   a warmed rung of page counts that reaches the longest live row, so
#   attention runs over what is live and not over ``max_len``;
# - **dead rows, filler and padding write nothing**: their index is the
#   out-of-bounds sentinel, which ``mode="drop"`` scatters ignore.  Sentinel
#   TABLE entries (>= P) read the pool's last page instead (the gather
#   clips): always a position the linear visibility mask hides (its
#   probability is exactly 0.0 after the float32 softmax, and pool contents
#   are finite), or a dead row whose logits the caller discards.
#
# The mathematics is the wide causal forward's: same visibility (``j <=
# pos``), the current token attends to its own cache entry, float32 softmax
# and logits.  A shorter extent changes which exact zeros are summed, so a
# stream served over one rung is TOKEN-identical to the same stream over
# another (or to the one-shot recompute), not bitwise-equal in its logits.


def _layer_rows(flat: jax.Array, n_layers: int, per_layer: int) -> jax.Array:
    """Per-layer scatter indices ``[L, ...]`` into a flat ``[L * per_layer,
    ...]`` view from per-layer indices ``flat`` (anything ``>= per_layer``
    is the sentinel and stays out of bounds in EVERY layer — it must not
    alias into the next layer's rows)."""
    base = jnp.arange(n_layers, dtype=jnp.int32) * per_layer
    base = base.reshape((n_layers,) + (1,) * flat.ndim)
    return jnp.where(flat[None] < per_layer, base + flat[None],
                     n_layers * per_layer)


def insert_pool(pages: jax.Array,      # [L, P, page_sz, W]
                new: jax.Array,        # [L, B, S, ...] (W values a position)
                flat_pos: jax.Array,   # [B, S // unit] int32 (OOB drop)
                scale: Optional[jax.Array] = None) -> jax.Array:
    """Scatter a prefill's rows into ONE pool's pages.  ``flat_pos``'s WIDTH
    says what one index moves: ``[B, S]`` — ``flat_pos[b, s]`` is the flat
    position ``page * page_sz + offset`` of prompt b's position s — or ``[B,
    S // page_sz]`` — entry j is the PHYSICAL PAGE of positions ``j *
    page_sz ..``, written whole (the chip's scatters cost by the index, not
    by the byte: 16x fewer).  A whole page takes the padded tail's rows with
    it, at positions no query can see until a later write replaces them; a
    prefilled prompt's pages are its stream's own.  Padding and filler carry
    the OOB sentinel (``P * page_sz``, or ``P`` for pages), so they can never
    touch a live page.  ``scale``: quantize to the int8 pool first."""
    L, P, ps, W = pages.shape
    S = new.shape[2]
    unit = S // flat_pos.shape[1]
    if unit not in (1, ps) or unit * flat_pos.shape[1] != S:
        raise ValueError(f"flat_pos {flat_pos.shape} addresses neither the "
                         f"{S} positions nor whole pages of {ps}")
    if scale is not None:
        new = quantize_kv(new, scale[:, None, None])
    per_layer = P * ps // unit
    idx = _layer_rows(flat_pos, L, per_layer).reshape(-1)
    view = (L * per_layer, W) if unit == 1 else (L * per_layer, unit, W)
    flat = pages.reshape(view)
    flat = flat.at[idx].set(
        new.reshape(idx.shape[0], *view[1:]).astype(pages.dtype), mode="drop")
    return flat.reshape(pages.shape)


def paged_insert(pages_k: jax.Array,   # [L, P, page_sz, H]
                 pages_v: jax.Array,
                 ks: jax.Array,        # [L, B, S, N, D] (prefill output)
                 vs: jax.Array,
                 flat_pos: jax.Array,  # [B, S // unit] int32 (OOB drop)
                 *, kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Scatter a prefill's K/V into pages: :func:`insert_pool` for K and
    for V."""
    ks_l, vs_l = kv_scales or (None, None)
    return (insert_pool(pages_k, ks, flat_pos, ks_l),
            insert_pool(pages_v, vs, flat_pos, vs_l))


def copy_pool(pages: jax.Array,
              src: jax.Array,      # [n] physical page ids (OOB = no-op)
              dst: jax.Array       # [n]
              ) -> jax.Array:
    """Copy-on-write page duplication in ONE pool: ``pages[dst[i]] =
    pages[src[i]]`` across all layers.  Unused rows carry the OOB sentinel
    ``P`` on both sides (``mode="fill"`` reads zeros, ``mode="drop"``
    discards the write), so ONE fixed row count serves every claim round."""
    got = jnp.take(pages, src, axis=1, mode="fill", fill_value=0)
    return pages.at[:, dst].set(got, mode="drop")


def gather_pool(pages: jax.Array,
                src: jax.Array       # [rows] physical page ids (OOB = 0s)
                ) -> jax.Array:
    """Export one stream's pages of ONE pool into a dense ``[L, rows,
    page_sz, W]`` payload for a KV handoff.  ``src`` is ALWAYS the fixed
    ``pages_per_stream`` extent, padded with the OOB sentinel ``P``
    (``mode="fill"`` reads zeros there), so one compiled program serves
    every stream regardless of how many pages it actually holds — the
    real page count rides the page ids, never the shape."""
    return jnp.take(pages, src, axis=1, mode="fill", fill_value=0)


def scatter_pool(pages: jax.Array,
                 payload: jax.Array,    # [L, rows, page_sz, W]
                 dst: jax.Array         # [rows] physical page ids (OOB drop)
                 ) -> jax.Array:
    """Import a handoff payload into freshly-allocated pages of ONE pool:
    the receive half of :func:`gather_pool`.  ``dst`` rows past the
    stream's real page count carry the OOB sentinel ``P`` and their
    (zero-filled) payload rows are dropped, so the import is the same ONE
    fixed-shape program for every stream."""
    return pages.at[:, dst].set(payload.astype(pages.dtype), mode="drop")


#: query rows (window positions x heads) up to which attention runs with the
#: heads FOLDED (:func:`_attend_folded`): one MXU tile of rows, so the
#: zero blocks of the expanded query cost nothing the chip would not spend
FOLD_ROWS = 128


def _attend_folded(q: jax.Array,     # [B, T, N, D]
                   k: jax.Array,     # [B, S, H]: pages as they lie, H = N * D
                   v: jax.Array,
                   bias: jax.Array   # [B, 1, T, S] additive, float32
                   ) -> jax.Array:
    """``dot_product_attention``'s mathematics on K/V whose heads are NOT
    split out: each query head is laid into its own ``D``-wide block of an
    ``H``-wide zero row, so ``[T * N, H] @ [H, S]`` scores every head
    against its own block (the zeros add exact 0.0) and ``probs @ V``'s
    block ``n`` of row ``(t, n)`` is head n's output.  Splitting ``[.., H]``
    into ``[.., N, D]`` is a relayout on the chip (``D`` = 64 is half a lane
    tile), and done to the gathered pages it cost more than the attention
    (PERF.md, PR 26); this form reads them once, as they lie, at ``N`` times
    the multiply-adds of rows that fill one MXU tile either way.  Scores in
    the compute dtype, softmax in float32."""
    B, T, N, D = q.shape
    S = k.shape[1]
    eye = jnp.eye(N, dtype=q.dtype)
    qe = (q[:, :, :, None, :] * eye[:, :, None]).reshape(B, T * N, N * D)
    scores = jnp.einsum("bqh,bkh->bqk", qe, k) * (D ** -0.5)
    scores = scores.reshape(B, T, N, S) + jnp.swapaxes(bias, 1, 2).astype(
        scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bqk,bkh->bqh", probs.reshape(B, T * N, S), v)
    return jnp.einsum("btnnd->btnd", out.reshape(B, T, N, N, D))


def _attend_paged(q: jax.Array,         # [B, 1, N, D]
                  pool_k: jax.Array,    # [L * P, page_sz, H]
                  pool_v: jax.Array,
                  page_ids: jax.Array,  # [B, MP] rows of the pools
                  lengths: jax.Array    # [B] keys a row sees (0: dead)
                  ) -> jax.Array:
    """:func:`_attend_folded` at one query position over keys that are NOT
    gathered: the same expanded query, its rows padded to the dtype's
    sublane tile, handed to the kernel that walks each row's live pages
    (``ops/paged.paged_decode``: scores accumulate in float32, softmax in
    float32, ``probs @ V`` in the compute dtype).  A dead row's output is
    zeros."""
    B, _, N, D = q.shape
    tile = 32 // q.dtype.itemsize
    Np = -(-N // tile) * tile
    eye = jnp.eye(Np, N, dtype=q.dtype)
    qe = (q[:, 0, None] * eye[:, :, None]).reshape(B, Np, N * D)
    out = paged.paged_decode(qe, pool_k, pool_v, page_ids, lengths,
                             scale=D ** -0.5)
    return jnp.einsum("btnnd->btnd", out[:, :N].reshape(B, 1, N, N, D))


def attend_form(T: int, int8: bool, mesh=None) -> str:
    """How :func:`paged_attend_layers` reads the cache at ``T`` query
    positions a row — the ONE statement of the choice: the body branches on
    it and the engine's ``decode.dispatch`` span reports it.  ``"kernel"``:
    the decode step (T = 1) over a float pool walks each row's own live
    pages (``ops/paged.py``); ``"gather"``: every launched row's whole page
    rung is gathered — a window of several positions, an int8 pool (its
    pages dequantize at read), and a step jitted over a ``mesh`` of more
    than one device, where a Mosaic kernel cannot stand
    (``ops.attention.pin_auto_for_mesh``, which says so once)."""
    if T != 1 or int8:
        return "gather"
    return ("kernel" if pin_auto_for_mesh(
        "auto", mesh, "paged decode attention") == "auto" else "gather")


def paged_attend_layers(params: Params, head: Params, cfg: BertConfig,
                        tokens: jax.Array,      # [B, T] int32
                        pages_k: jax.Array,     # [L, P, page_sz, H]
                        pages_v: jax.Array,
                        page_table: jax.Array,  # [B, <= MP] int32 (sentinel P)
                        start: jax.Array,       # [B] abs pos of tokens[:, 0]
                        nreal: Optional[jax.Array] = None,  # [B] real lengths
                        *, logits_at: str = "last",
                        kv_scales: Optional[Tuple[jax.Array,
                                                  jax.Array]] = None,
                        dtype=jnp.float32, unroll=True, mesh=None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The ONE body of every paged program: ``tokens[b, t]`` sits at
    absolute position ``start[b] + t``, writes its K/V through the page
    table (in place: module note above), and attends to key positions
    ``<= start[b] + t`` of the pages ``page_table[b]`` names — the extent
    attended over is the table's width times the page size, so the caller
    chooses it by the table it hands in.

    ``nreal`` (``None``: every token is real) marks padded window slots
    ``t >= nreal[b]``, whose writes land out of bounds; rows with
    ``nreal == 0`` or a sentinel table are filler that writes nothing and
    whose logits are garbage the caller discards.  ``logits_at`` says where
    the LM head is read: ``"last"`` — each row's last real token,
    ``[B, vocab]`` (the decode step, T = 1, and the suffix chunk) — or
    ``"all"`` — every position, ``[B, T, vocab]`` (speculative verify).
    ``kv_scales`` = (k_scale, v_scale) ``[L, N, D]`` switches the pool to
    int8: new rows quantize before the write and the gathered pages
    dequantize at read, so the current token's K/V round-trips through the
    cache like everyone else's.  ``mesh``: what the caller's ``jit`` runs
    over, where that is more than one device (:func:`attend_form`)."""
    _check_dense_trunk(params["layers"])
    if logits_at not in ("last", "all"):
        raise ValueError(f"logits_at must be 'last' or 'all', "
                         f"got {logits_at!r}")
    L, P, ps, H = pages_k.shape
    N, D = cfg.num_heads, cfg.head_dim
    B, T = tokens.shape
    MP = page_table.shape[1]
    extent = MP * ps
    start = start.astype(jnp.int32)
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)   # [B, T]
    x, _ = bert.embed(params, cfg, tokens, jnp.zeros_like(tokens),
                      dtype=dtype, deterministic=True,
                      position_ids=positions)
    # linear visibility, never a [S, S] term: query t sees key j iff
    # j <= start + t (shared prefix + the window's own causal triangle)
    vis = (jnp.arange(extent, dtype=jnp.int32)[None, None, :]
           <= positions[:, :, None])                        # [B, T, extent]
    bias = jnp.where(vis, 0.0, NEG_INF).astype(jnp.float32)[:, None]
    # write rows: padded window slots, dead rows (sentinel tables) and
    # positions past the table land on the sentinel in every layer
    real = positions < extent
    if nreal is not None:
        nreal = nreal.astype(jnp.int32)
        real &= jnp.arange(T, dtype=jnp.int32)[None, :] < nreal[:, None]
    phys = jnp.take_along_axis(
        page_table, jnp.clip(positions // ps, 0, MP - 1), axis=1)  # [B, T]
    wrows = _layer_rows(
        jnp.where(real & (phys < P), phys * ps + positions % ps, P * ps),
        L, P * ps).reshape(L, B * T)
    # read pages: a sentinel entry clips to the pool's last page (module
    # note)
    rpages = _layer_rows(page_table, L, P)                   # [L, B, MP]
    # few query rows (the decode step, a verify window): heads stay folded
    # and the pages are read as they lie; a prefill-sized window splits the
    # heads out (a relayout of its few rows' pages) rather than pay N times
    # the multiply-adds
    fold = T * N <= FOLD_ROWS
    attend = (_attend_folded if fold else
              functools.partial(dot_product_attention, impl="auto"))

    def put(pages, rows, new, scale):
        if scale is not None:
            new = quantize_kv(new, scale)
        flat = pages.reshape(L * P * ps, H)
        flat = flat.at[rows].set(
            new.reshape(B * T, H).astype(pages.dtype), mode="drop")
        return flat.reshape(pages.shape)

    def get(pages, idx, scale):
        got = jnp.take(pages.reshape(L * P, ps, H), idx, axis=0,
                       mode="clip").reshape(B, extent, H)
        if scale is not None:
            got = dequantize_kv(got, scale.reshape(H), dtype)
        return got if fold else got.reshape(B, extent, N, D)

    if attend_form(T, kv_scales is not None, mesh) == "kernel":
        # no rung is gathered: a kernel walks each row's own live pages
        # where they lie (``ops/paged.py``).  A live row sees ``pos + 1``
        # keys, a dead one (it writes nothing: ``wrows``' test) none
        lengths = jnp.where(real & (phys < P), positions + 1, 0)[:, 0]

        def read(q, pk, pv, idx, *_):
            return _attend_paged(q, pk.reshape(L * P, ps, H),
                                 pv.reshape(L * P, ps, H), idx, lengths)
    else:
        def read(q, pk, pv, idx, ks_l, vs_l):
            return attend(q, get(pk, idx, ks_l), get(pv, idx, vs_l), bias)

    def layer(carry, scanned):
        x, pk, pv = carry
        lp, rows, idx, ks_l, vs_l = scanned
        q, k_new, v_new = _qkv(x, lp, cfg, dtype)           # [B, T, N, D]
        pk = put(pk, rows, k_new, ks_l)
        pv = put(pv, rows, v_new, vs_l)
        attn = read(q, pk, pv, idx, ks_l, vs_l)
        return (_finish_layer(x, lp, cfg, attn, dtype), pk, pv), None

    xs = (params["layers"], wrows, rpages) + (kv_scales or (None, None))
    (x, pages_k, pages_v), _ = jax.lax.scan(
        layer, (x, pages_k, pages_v), xs, unroll=unroll)
    if logits_at == "last":
        last = (jnp.zeros((B,), jnp.int32) if nreal is None
                else jnp.clip(nreal - 1, 0, T - 1))
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)   # [B, 1, H]
    logits = lm_logits(params, head, cfg, x, dtype=dtype)
    return (logits[:, 0] if logits_at == "last" else logits,
            pages_k, pages_v)


def paged_decode_step(params: Params, head: Params, cfg: BertConfig,
                      tokens: jax.Array,      # [B, 1] int32
                      pages_k: jax.Array,     # [L, P, page_sz, H]
                      pages_v: jax.Array,
                      page_table: jax.Array,  # [B, <= MP] int32 (sentinel P)
                      pos: jax.Array,         # [B] int32 write positions
                      **kw) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step over a paged cache — the core at T = 1: write the
    current token's K/V through the table, attend over the table's extent,
    next-token logits ``[B, vocab]``.  The table is data, so one compiled
    program serves every step at a given table width."""
    return paged_attend_layers(params, head, cfg, tokens, pages_k, pages_v,
                               page_table, pos, **kw)


def paged_chunk_step(params: Params, head: Params, cfg: BertConfig,
                     tokens: jax.Array,      # [B, T] int32 (suffix chunk)
                     pages_k: jax.Array, pages_v: jax.Array,
                     page_table: jax.Array,  # [B, MP] int32 (sentinel P)
                     start: jax.Array,       # [B] absolute pos of tokens[:,0]
                     nreal: jax.Array,       # [B] real chunk lengths (0 ok)
                     **kw) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Suffix prefill against a paged cache: the prompt's SHARED prefix
    pages already hold K/V (a prefix-index hit), so only the divergent
    suffix runs — the core at T = the bucket, returning each row's LAST
    real token's next-token logits ``[B, vocab]`` like :func:`prefill`."""
    return paged_attend_layers(params, head, cfg, tokens, pages_k, pages_v,
                               page_table, start, nreal, **kw)


def paged_verify_step(params: Params, head: Params, cfg: BertConfig,
                      tokens: jax.Array,      # [B, K1] int32 (spec window)
                      pages_k: jax.Array, pages_v: jax.Array,
                      page_table: jax.Array,  # [B, MP] int32 (sentinel P)
                      start: jax.Array,       # [B] abs pos of tokens[:,0]
                      nreal: jax.Array,       # [B] real window lengths
                      **kw) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative verify: score the pending token plus k drafted tokens
    in ONE prefill-shaped call — the core with the LM head over EVERY
    window position, ``[B, K1, vocab]`` fp32, so the caller can take the
    greedy target at each draft offset.  K/V for the whole window is
    written eagerly; rejected positions stay in the cache as stale entries
    that no later query can see (the visibility mask is position-based) and
    the next round overwrites them in place."""
    return paged_attend_layers(params, head, cfg, tokens, pages_k, pages_v,
                               page_table, start, nreal, logits_at="all",
                               **kw)


# ------------------------------------------------------- infilling scoring

def infill_logits(params: Params, head: Params, cfg: BertConfig,
                  input_ids: jax.Array,       # [B, S] int32
                  attention_mask: jax.Array,  # [B, S] {0,1}
                  *, dtype=jnp.float32, attn_impl: str = "auto",
                  unroll=True) -> jax.Array:
    """MLM-infilling scorer: the BIDIRECTIONAL trunk (BERT's native
    objective — no causal mask) + the LM head over every position,
    [B, S, vocab] fp32.  The serve engine reads the rows at ``[MASK]``
    positions; everything (trunk, head, tied decoder) is shared with the
    causal path, so one checkpoint answers both "continue this" and
    "fill this in"."""
    zeros = jnp.zeros_like(input_ids)
    hidden = bert.encode(params, cfg, input_ids, zeros, attention_mask,
                         dtype=dtype, deterministic=True,
                         attn_impl=attn_impl, unroll=unroll)
    return lm_logits(params, head, cfg, hidden, dtype=dtype)


# ------------------------------------------------------------- calibration

def calibrate_kv_scales(params: Params, cfg: BertConfig, *,
                        seq_len: Optional[int] = None,
                        dtype=jnp.float32
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(layer, head, channel) symmetric int8 K/V scale tables
    ``[L, N, D]`` from a SEEDED synthetic causal forward — no corpus, no
    device requirement, and deterministic in the params alone, so the
    offline artifact (``scripts/quantize_ckpt.py --kv_calib``) and engine
    self-calibration at warmup produce byte-identical tables."""
    seq_len = int(seq_len or min(128, cfg.max_position))
    # a raw host tree (the offline script's load_raw) must compute through
    # the SAME backend as device params — numpy operands would dispatch
    # numpy's BLAS on the first matmul and the tables would drift by ULPs
    params = jax.tree_util.tree_map(jnp.asarray, params)
    key = jax.random.key(CALIB_SEED)
    ids = jax.random.randint(key, (CALIB_ROWS, seq_len), 0, cfg.vocab_size,
                             dtype=jnp.int32)
    mask = jnp.ones((CALIB_ROWS, seq_len), jnp.int32)
    x, _ = bert.embed(params, cfg, ids, jnp.zeros_like(ids), dtype=dtype,
                      deterministic=True)
    _, ks, vs = run_layers_kv(params["layers"], cfg, x,
                              bias=mask_bias(mask, jnp.float32),
                              causal=True, dtype=dtype)
    # amax over (rows, positions) -> [L, N, D]; zero channels get scale 1
    def table(t):
        amax = np.abs(np.asarray(t, np.float32)).max(axis=(1, 2))
        return np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)

    return table(ks), table(vs)
