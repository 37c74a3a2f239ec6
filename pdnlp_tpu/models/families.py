"""The ONE table the serving engines look a model family up in.

``serve.decode``'s engines are written against this seam, not against a
model file: what a family stores a cached position as (the pools' widths),
how its weights come into being, and the step functions its jitted programs
call.  ``of(cfg)`` is looked up once, at construction; no call site asks
which family it serves.

A step function takes and returns the cache as a TUPLE of pools.  A POOL is
one KIND of cached value: an array ``[layers, P, page_sz, width]`` with its
OWN width (``pool_widths``) and its OWN count of layers (``pool_layers``: a
count a pool, or one for all), over the pages of ONE table, ONE allocator
and ONE prefix index — page ``p`` names the same positions in every pool, so
a shared prefix shares all of them.  Twin K and V pools for the BERT causal
LM (``models/decoder.py``); one latent pool for the latent-attention decoder
(``models/latent_moe.py``) and, where it has an indexer, a second, narrower
pool of index keys over the layers that score alone; twin pools over the GQA
layers alone for the hybrid (``models/hybrid_linear.py``).  ``pool_shapes``
and ``token_bytes`` are the one place that reads the two.  Beside the pools
a step function takes and returns a
TUPLE of per-slot state arrays ``[slots, ...]`` (``state_shapes``: the
hybrid's recurrent states and convolution tails; empty for the other two,
whose programs an empty tuple adds no operand to).  It returns ``aux``
beside the logits: what a launch counted (``latent_moe.no_load``: assignments
to each held expert, and the rows the experts' products computed), or
``None``; a latent configuration with an indexer adds the positions its
real queries saw and picked (``latent_moe.no_picks``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import numpy as np

from pdnlp_tpu.models import bert, decoder, hybrid_linear, latent_moe


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    #: (key, cfg) -> the trunk's parameters / the LM head's
    init_params: Callable
    init_head: Callable
    #: cfg -> the width of each pool (values a position a layer)
    pool_widths: Callable
    #: (params, head, cfg, ids, mask, last_pos, dtype)
    #:   -> (logits [B, V], aux, new rows: one [L, B, S, ...] per pool, each
    #:       prompt's final state: one [B, ...] per state array)
    prefill: Callable
    #: (params, head, cfg, tokens, pools, states, table, start, nreal,
    #:  logits_at, kv_scales, dtype, mesh: what the program is jitted over)
    #:   -> (logits, aux, pools, states)
    attend: Callable
    #: cfg -> how many layers a cached position lives in: one count a pool,
    #: or ONE for every pool
    pool_layers: Callable = lambda cfg: cfg.num_layers
    #: (cfg, extent) -> positions of a row's ``extent`` a decode step that
    #: gathers reads a layer
    read_extent: Callable = lambda cfg, extent: extent
    #: (cfg, slots) -> ((shape, dtype or None = the compute dtype), ...) of
    #: what a SLOT keeps besides its pages
    state_shapes: Callable = lambda cfg, slots: ()
    #: weights are made on the device and the template is shapes only (a
    #: model of gigabytes is never held twice)
    lazy_weights: bool = False
    #: what the family can be asked for besides prefill / chunk / decode
    int8: bool = True             # int8 weights or an int8 cache
    verify: bool = True           # the speculative pair's scoring window
    handoff: bool = True          # exporting / importing a stream's pages
    prefix: bool = True           # sharing a prompt's prefix pages (and the
                                  # suffix chunk that follows a hit)
    #: (T, int8 pool?, mesh) -> how ``attend`` reads the cache: "kernel"
    #: (each row's own live pages, ``decoder.attend_form``) or "gather"
    #: (the page rung of every launched row)
    attend_form: Callable = lambda T, int8, mesh: "gather"

    def refuse(self, what: str, use: str) -> None:
        raise ValueError(
            f"the {self.name} model family does not support {what}; {use}")


def _bert_prefill(params, head, cfg, ids, mask, last_pos, dtype):
    logits, ks, vs = decoder.prefill(params, head, cfg, ids, mask, last_pos,
                                     dtype=dtype)
    return logits, None, (ks, vs), ()


def _bert_attend(params, head, cfg, tokens, pools, states, table, start,
                 nreal, logits_at, kv_scales, dtype, mesh=None):
    logits, pk, pv = decoder.paged_attend_layers(
        params, head, cfg, tokens, pools[0], pools[1], table, start, nreal,
        logits_at=logits_at, kv_scales=kv_scales, dtype=dtype, mesh=mesh)
    return logits, None, (pk, pv), states


def _latent_prefill(params, head, cfg, ids, mask, last_pos, dtype):
    logits, load, news = latent_moe.prefill(
        params, head, cfg, ids, mask, last_pos, dtype=dtype)
    return logits, load, news if cfg.index_n_heads else (news,), ()


def _latent_attend(params, head, cfg, tokens, pools, states, table, start,
                   nreal, logits_at, kv_scales, dtype, mesh=None):
    # the latents alone, or the pair with the index keys
    logits, load, pool = latent_moe.paged_attend(
        params, head, cfg, tokens, pools if cfg.index_n_heads else pools[0],
        table, start, nreal, dtype=dtype)
    return logits, load, pool if cfg.index_n_heads else (pool,), states


def _latent_pools(cfg):
    """(layers, width) of the latents' pool and, with an indexer, of the
    index keys' (the layers that score alone)."""
    pools = ((cfg.num_layers, cfg.cache_width),)
    if cfg.index_n_heads:
        pools += ((cfg.num_index_layers, cfg.index_cache_width),)
    return pools


def _hybrid_prefill(params, head, cfg, ids, mask, last_pos, dtype):
    return hybrid_linear.prefill(params, head, cfg, ids, mask, last_pos,
                                 dtype=dtype)


def _hybrid_attend(params, head, cfg, tokens, pools, states, table, start,
                   nreal, logits_at, kv_scales, dtype, mesh=None):
    if tokens.shape[1] != 1:
        # a window of several positions against a cache exists only after a
        # prefix hit or in the speculative pair: both refused at construction
        raise NotImplementedError(
            "the hybrid_linear family decodes one position a row")
    return hybrid_linear.paged_decode(params, head, cfg, tokens, pools,
                                      states, table, start, dtype=dtype)


FAMILIES = {
    "bert": Family(
        name="bert", init_params=bert.init_params,
        init_head=decoder.init_lm_head,
        pool_widths=lambda cfg: (cfg.hidden_size, cfg.hidden_size),
        prefill=_bert_prefill, attend=_bert_attend,
        attend_form=decoder.attend_form),
    "latent_moe": Family(
        name="latent_moe", init_params=latent_moe.init_params,
        init_head=latent_moe.init_head,
        pool_widths=lambda cfg: tuple(w for _, w in _latent_pools(cfg)),
        prefill=_latent_prefill, attend=_latent_attend,
        pool_layers=lambda cfg: tuple(n for n, _ in _latent_pools(cfg)),
        read_extent=lambda cfg, extent: (
            min(cfg.index_topk, extent) if cfg.index_n_heads else extent),
        lazy_weights=True, int8=False, verify=False,
        handoff=False),
    # no snapshot of the recurrent state exists at a page boundary, so
    # nothing that resumes, rolls back or moves a stream mid-way is offered
    "hybrid_linear": Family(
        name="hybrid_linear", init_params=hybrid_linear.init_params,
        init_head=hybrid_linear.init_head,
        pool_widths=lambda cfg: (cfg.kv_width, cfg.kv_width),
        prefill=_hybrid_prefill, attend=_hybrid_attend,
        pool_layers=lambda cfg: cfg.num_gqa_layers,
        state_shapes=hybrid_linear.state_shapes,
        lazy_weights=True, int8=False, verify=False, handoff=False,
        prefix=False),
}


def of(cfg) -> Family:
    return FAMILIES[cfg.family]


def pool_shapes(cfg) -> Tuple[Tuple[int, int], ...]:
    """(layers, width) of every pool of ``cfg``'s family."""
    family = of(cfg)
    widths, layers = family.pool_widths(cfg), family.pool_layers(cfg)
    if isinstance(layers, int):
        layers = (layers,) * len(widths)
    return tuple(zip(layers, widths))


def token_bytes(cfg, kv_dtype) -> int:
    """Bytes one cached position takes over every pool's layers."""
    return int(sum(n * w for n, w in pool_shapes(cfg))
               * np.dtype(kv_dtype).itemsize)


def insert(pools: Tuple, news: Tuple, flat_pos, kv_scales: Optional[Tuple]
           ) -> Tuple:
    """A prefill's new rows into every pool (``decoder.insert_pool``)."""
    scales = kv_scales or (None,) * len(pools)
    return tuple(decoder.insert_pool(p, n, flat_pos, s)
                 for p, n, s in zip(pools, news, scales))


def insert_states(states: Tuple, news: Tuple, slot_ids) -> Tuple:
    """A prefill's final states ``[rows, ...]`` into the rows ``slot_ids`` of
    the per-slot arrays ``[slots, ...]`` (a filler row carries an id past
    the end and is dropped)."""
    return tuple(s.at[slot_ids].set(n.astype(s.dtype), mode="drop")
                 for s, n in zip(states, news))
