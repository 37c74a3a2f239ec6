"""The ONE table the serving engines look a model family up in.

``serve.decode``'s engines are written against this seam, not against a
model file: what a family stores a cached position as (the pools' widths),
how its weights come into being, and the step functions its jitted programs
call.  ``of(cfg)`` is looked up once, at construction; no call site asks
which family it serves.

A step function takes and returns the cache as a TUPLE of pools ``[L, P,
page_sz, width]`` — twin K and V pools for the BERT causal LM
(``models/decoder.py``), one latent pool for the latent-attention decoder
(``models/latent_moe.py``) — and returns ``aux`` beside the logits: what a
launch counted (assignments to each held expert), or ``None``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import numpy as np

from pdnlp_tpu.models import bert, decoder, latent_moe


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    #: (key, cfg) -> the trunk's parameters / the LM head's
    init_params: Callable
    init_head: Callable
    #: cfg -> the width of each pool (values a position a layer)
    pool_widths: Callable
    #: (params, head, cfg, ids, mask, last_pos, dtype)
    #:   -> (logits [B, V], aux, new rows: one [L, B, S, ...] per pool)
    prefill: Callable
    #: (params, head, cfg, tokens, pools, table, start, nreal, logits_at,
    #:  kv_scales, dtype) -> (logits, aux, pools)
    attend: Callable
    #: weights are made on the device and the template is shapes only (a
    #: model of gigabytes is never held twice)
    lazy_weights: bool = False
    #: what the family can be asked for besides prefill / chunk / decode
    int8: bool = True             # int8 weights or an int8 cache
    verify: bool = True           # the speculative pair's scoring window
    handoff: bool = True          # exporting / importing a stream's pages

    def refuse(self, what: str, use: str) -> None:
        raise ValueError(
            f"the {self.name} model family does not support {what}; {use}")


def _bert_prefill(params, head, cfg, ids, mask, last_pos, dtype):
    logits, ks, vs = decoder.prefill(params, head, cfg, ids, mask, last_pos,
                                     dtype=dtype)
    return logits, None, (ks, vs)


def _bert_attend(params, head, cfg, tokens, pools, table, start, nreal,
                 logits_at, kv_scales, dtype):
    logits, pk, pv = decoder.paged_attend_layers(
        params, head, cfg, tokens, pools[0], pools[1], table, start, nreal,
        logits_at=logits_at, kv_scales=kv_scales, dtype=dtype)
    return logits, None, (pk, pv)


def _latent_prefill(params, head, cfg, ids, mask, last_pos, dtype):
    logits, counts, latents = latent_moe.prefill(
        params, head, cfg, ids, mask, last_pos, dtype=dtype)
    return logits, counts, (latents,)


def _latent_attend(params, head, cfg, tokens, pools, table, start, nreal,
                   logits_at, kv_scales, dtype):
    logits, counts, pool = latent_moe.paged_attend(
        params, head, cfg, tokens, pools[0], table, start, nreal,
        dtype=dtype)
    return logits, counts, (pool,)


FAMILIES = {
    "bert": Family(
        name="bert", init_params=bert.init_params,
        init_head=decoder.init_lm_head,
        pool_widths=lambda cfg: (cfg.hidden_size, cfg.hidden_size),
        prefill=_bert_prefill, attend=_bert_attend),
    "latent_moe": Family(
        name="latent_moe", init_params=latent_moe.init_params,
        init_head=latent_moe.init_head,
        pool_widths=lambda cfg: (cfg.cache_width,),
        prefill=_latent_prefill, attend=_latent_attend,
        lazy_weights=True, int8=False, verify=False,
        handoff=False),
}


def of(cfg) -> Family:
    return FAMILIES[cfg.family]


def token_bytes(cfg, kv_dtype) -> int:
    """Bytes one cached position takes over every layer and pool."""
    return int(cfg.num_layers * sum(of(cfg).pool_widths(cfg))
               * np.dtype(kv_dtype).itemsize)


def insert(pools: Tuple, news: Tuple, flat_pos, kv_scales: Optional[Tuple]
           ) -> Tuple:
    """A prefill's new rows into every pool (``decoder.insert_pool``)."""
    scales = kv_scales or (None,) * len(pools)
    return tuple(decoder.insert_pool(p, n, flat_pos, s)
                 for p, n, s in zip(pools, news, scales))
