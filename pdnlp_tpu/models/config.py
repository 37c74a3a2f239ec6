"""Model configuration registry.

The reference builds its model as HF ``BertConfig`` +
``BertForSequenceClassification.from_pretrained`` with ``num_labels=6``
(``/root/reference/single-gpu-cls.py:252-255``).  Here the architecture is a
first-class config: one frozen dataclass, a named registry (``bert-base``
matches ``chinese-bert-wwm-ext``'s shape: 12L/768H/12 heads, vocab 21128),
plus small variants used by tests and the multichip dryrun.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 21_128
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    attn_dropout: float = 0.1
    num_labels: int = 6
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    gelu: str = "erf"             # "erf" = exact (HF BertConfig
                                  # hidden_act="gelu", the reference model);
                                  # "tanh" = polynomial approximation —
                                  # measured +7% fused-step rate at batch 64
                                  # on v5e and +0.7pt fine-tune accuracy
                                  # when pretrained with it end to end
                                  # (records older than the ledger,
                                  # removed; not re-measured)
    # --- mixture-of-experts (0 experts = dense MLP; no reference twin) ---
    moe_experts: int = 0          # experts per layer's MLP
    moe_top_k: int = 2            # experts combined per token
    moe_aux_coef: float = 0.01    # Switch-style load-balancing loss weight
    moe_dispatch: str = "grouped" # "grouped": capacity-based gather +
                                  # per-expert matmuls, O(k*capacity) FFN
                                  # cost; "dense": every expert computes
                                  # every token, O(E) — exact, no drops,
                                  # the small-E fallback and parity oracle
    moe_capacity_factor: float = 1.25  # slots per expert =
                                  # ceil(cf * k * tokens / E); tokens over
                                  # capacity fall back to the residual path

    #: which entry of ``models.families`` runs this config (a class
    #: attribute, not a field: a preset cannot change its family)
    family = "bert"

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    def replace(self, **kw) -> "BertConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """A pre-norm decoder with latent attention (MLA) and sparse experts,
    served only (``models/latent_moe.py``).  Field names are the published
    ``config.json``'s where it has one.  Three published models are run
    through it: A.X-K1 (https://huggingface.co/skt/A.X-K1/blob/main/config.json),
    whose widths the DEFAULTS are — so a preset of any other model states
    EVERY width, or it would inherit A.X-K1's —, Xing4.0-29B-A4B
    (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json)
    and GLM-5.2 (https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json).

    ``experts_held`` / ``expert_first`` say which of the ``n_routed_experts``
    THIS process holds (expert parallelism's share): the router keeps its
    full width, its groups and its experts per token; only the held
    experts' part of a layer's result is computed.  ``experts_held ==
    n_routed_experts`` holds a layer's experts whole.

    ``hc_mult`` > 1 turns the residual into that many STREAMS mixed by
    manifold-constrained hyper-connections (``models/hyper_connections.py``:
    every sub-layer reads the streams through a learned, input-dependent
    row, writes through another and carries them through a doubly-stochastic
    matrix made by ``hc_sinkhorn_iters`` Sinkhorn steps whose denominators
    carry ``hc_eps``, from logits clipped to ``hc_res_clamp``); 1 is the
    plain residual ``x + F(x)``, with no mixing leaf and no mixing
    operation.  ``selection_bias``: the router CHOOSES experts by ``score +
    bias`` (a learned leaf a layer) and GATES by the score alone
    (``topk_method: "noaux_tc"``); False: no such leaf, chosen by score.

    ``index_n_heads`` > 0 makes the attention a LEARNED SPARSE one: a layer
    whose entry of ``indexer_types`` (one a layer HELD, the first ``"full"``)
    is ``"full"`` holds a lightning indexer — ``index_n_heads`` heads of
    ``index_head_dim``, the first ``qk_rope_head_dim`` values of each
    rotated — that scores every visible position and picks the
    ``index_topk`` best; a ``"shared"`` layer holds no indexer and uses the
    last ``"full"`` layer's picks; the softmax runs over the picked positions
    alone.  The ``"full"`` layers' index keys are a SECOND pool of the page
    cache (``index_cache_width`` wide over ``num_index_layers`` layers,
    through the latents' page table).  0, the default: no indexer leaf, no
    second pool, every cached position attended — today's programs, not a
    degenerate selection.

    Rotary positions: ``rope_factor`` 1 is the plain rotary table (``theta
    ** (-2i/d)``, scale ``(d_nope + d_rope) ** -0.5``); above 1, yarn.  The
    program rotates the pairs ``(x[i], x[i + d/2])``.  A model published
    with INTERLEAVED pairs ``(x[2i], x[2i+1])`` is given its rotary columns
    de-interleaved (of ``q_b_rope``, of ``kv_a``'s rotary part and of the
    indexer's two projections with the index key's norm): a permutation of
    columns that both sides of every dot share, so every score is the same
    (``benchmark/reference/glm52.py`` ``program_layout``; a test holds the
    two equal)."""
    vocab_size: int = 163_840
    hidden_size: int = 7168
    num_layers: int = 61          # leading dense layers + expert layers
    first_k_dense: int = 1
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18_432     # the dense layers' feed-forward
    moe_intermediate_size: int = 2048   # one expert's (and the shared one's)
    n_routed_experts: int = 192         # the router's width
    experts_held: int = 192             # ... of which this process holds
    expert_first: int = 0               # ... starting at this one
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    rope_factor: float = 32.0           # yarn
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position: int = 131_072
    hc_mult: int = 1                    # residual streams (1: x + F(x))
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    selection_bias: bool = False        # choose by score + bias, gate by score
    index_n_heads: int = 0              # the indexer's heads (0: no indexer)
    index_head_dim: int = 128
    index_topk: int = 2048              # positions a query attends to
    indexer_types: Tuple[str, ...] = ()  # "full" | "shared", a layer held
    weight_dtype: str = "bfloat16"      # how the weights are STORED

    family = "latent_moe"

    def __post_init__(self):
        if not self.index_n_heads:
            return
        kinds = tuple(self.indexer_types)
        if (len(kinds) != self.num_layers or kinds[:1] != ("full",)
                or set(kinds) - {"full", "shared"}):
            raise ValueError(
                f"indexer_types {kinds} is not one of 'full' | 'shared' for "
                f"each of the {self.num_layers} layers held, the first "
                "'full' (a 'shared' layer uses the picks of the last 'full' "
                "one before it)")
        if self.hc_mult > 1 or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                "the indexer reads a one-stream residual and rotates the "
                "first qk_rope_head_dim values of an index head")

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """The layers that hold an indexer and cache index keys."""
        return tuple(l for l, kind in enumerate(self.indexer_types)
                     if kind == "full") if self.index_n_heads else ()

    @property
    def num_index_layers(self) -> int:
        return len(self.full_layers)

    @property
    def index_cache_width(self) -> int:
        """One cached index key as its pool holds it: whole lane tiles."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def latent_width(self) -> int:
        """One cached position of one layer: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """... as the pool holds it: padded to whole lane tiles of 128.  A
        ``[page_sz, 576]`` tail is 4.5 tiles, and the chip's default layout
        then puts the PAGE axis on the lanes and every program converts the
        whole pool (read from a described-v5e compile, PERF.md section 6)."""
        return -(-self.latent_width // 128) * 128

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    def replace(self, **kw) -> "LatentMoEConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class HybridLinearConfig:
    """A pre-norm decoder whose layers are of TWO kinds in a fixed period:
    one softmax GQA layer without positions, gated, then ``gqa_interval``
    gated delta-rule linear-attention layers with channel-wise decay and a
    short causal convolution; sparse experts in every layer; served only
    (``models/hybrid_linear.py``).  Field names are the published
    ``config.json``'s where it has one; the defaults are Solar-Open2-250B's
    (https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json).

    ``experts_held`` / ``expert_first``: the share, as ``LatentMoEConfig``'s
    (the expert layer IS ``models/latent_moe.py``'s, told the same way)."""
    vocab_size: int = 196_608
    hidden_size: int = 4096
    num_layers: int = 48          # whole periods of 1 + gqa_interval layers
    gqa_interval: int = 3         # linear layers after each GQA layer
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    linear_num_heads: int = 64
    linear_head_dim: int = 128    # d_k = d_v of the recurrent state
    conv_kernel: int = 4          # the short convolution's width
    low_rank: int = 128           # the decay's and the gate's inner width
    moe_intermediate_size: int = 1280   # one expert's (and the shared one's)
    n_routed_experts: int = 320         # the router's width
    experts_held: int = 320             # ... of which this process holds
    expert_first: int = 0               # ... starting at this one
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 1                    # a plain top-k over all experts
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position: int = 1_048_576       # no position table: a length limit
    weight_dtype: str = "bfloat16"      # how the weights are STORED

    family = "hybrid_linear"

    def __post_init__(self):
        if self.num_layers % self.period:
            raise ValueError(
                f"{self.num_layers} layers are no whole number of periods of "
                f"{self.period} (1 GQA + {self.gqa_interval} linear)")

    @property
    def period(self) -> int:
        return 1 + self.gqa_interval

    def is_gqa(self, l: int) -> bool:
        """Layer ``l`` is a softmax GQA layer (0, 4, 8, ...)."""
        return l % self.period == 0

    @property
    def num_gqa_layers(self) -> int:
        """The layers that PAGE: a cached position lives in these alone."""
        return self.num_layers // self.period

    @property
    def num_linear_layers(self) -> int:
        """The layers whose memory is a per-stream recurrent state."""
        return self.num_layers - self.num_gqa_layers

    @property
    def kv_width(self) -> int:
        """One cached position's keys (or values) of one GQA layer."""
        return self.num_kv_heads * self.head_dim

    @property
    def linear_width(self) -> int:
        """q, k or v of a linear layer, all heads."""
        return self.linear_num_heads * self.linear_head_dim

    def replace(self, **kw) -> "HybridLinearConfig":
        return dataclasses.replace(self, **kw)


def _xing4(**kw) -> LatentMoEConfig:
    """Xing4.0-29B-A4B's published ``config.json``, EVERY field stated (a
    default left standing would be A.X-K1's); ``kw`` cuts it."""
    fields = dict(
        vocab_size=131_072, hidden_size=3584, num_layers=40, first_k_dense=2,
        num_heads=32, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=9216,
        moe_intermediate_size=1024, n_routed_experts=64, experts_held=64,
        expert_first=0, num_experts_per_tok=4, n_shared_experts=1, n_group=1,
        topk_group=1, routed_scaling_factor=2.0, rms_norm_eps=1e-6,
        rope_theta=10_000.0, rope_factor=64.0, rope_original_max=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, max_position=262_144, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0),
        selection_bias=True, index_n_heads=0, index_head_dim=128,
        index_topk=2048, indexer_types=(), weight_dtype="bfloat16")
    fields.update(kw)
    return LatentMoEConfig(**fields)


def _glm52(**kw) -> LatentMoEConfig:
    """GLM-5.2's published ``config.json``, EVERY field stated (a default
    left standing would be A.X-K1's); ``kw`` cuts it.  ``indexer_types`` is
    the published list (``full`` for layers 0-2 and every fourth from 6)."""
    fields = dict(
        vocab_size=154_880, hidden_size=6144, num_layers=78, first_k_dense=3,
        num_heads=64, q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        intermediate_size=12_288, moe_intermediate_size=2048,
        n_routed_experts=256, experts_held=256, expert_first=0,
        num_experts_per_tok=8, n_shared_experts=1, n_group=1, topk_group=1,
        routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=8_000_000.0,
        rope_factor=1.0, rope_original_max=1_048_576, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        max_position=1_048_576, hc_mult=1, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_res_clamp=(-30.0, 30.0), selection_bias=True, index_n_heads=32,
        index_head_dim=128, index_topk=2048,
        indexer_types=tuple("full" if l < 3 or (l - 2) % 4 == 0 else "shared"
                            for l in range(78)),
        weight_dtype="bfloat16")
    fields.update(kw)
    return LatentMoEConfig(**fields)


#: published layers 2-8 of GLM-5.2: the last dense layer and six expert layers
_GLM52_HELD = ("full", "shared", "shared", "shared", "full", "shared",
               "shared")


_REGISTRY = {
    # chinese-bert-wwm-ext shape (BERT-base, ~102M params at vocab 21128)
    "bert-base": BertConfig(),
    # scaled-down variants for CI / virtual-mesh dryruns
    "bert-small": BertConfig(hidden_size=512, num_layers=4, num_heads=8,
                             intermediate_size=2048),
    "bert-tiny": BertConfig(hidden_size=128, num_layers=2, num_heads=2,
                            intermediate_size=512, max_position=128),
    # MoE variants: the dense MLP becomes moe_experts gated experts (the
    # expert-parallel "ep" sharding mode splits them over an "expert" axis)
    "bert-base-moe": BertConfig(moe_experts=4),
    "bert-tiny-moe": BertConfig(hidden_size=128, num_layers=2, num_heads=2,
                                intermediate_size=512, max_position=128,
                                moe_experts=4),
    # long-context variants: a 4x position table for the sequence-parallel
    # (ring attention) path, whose whole point is sequences no single
    # device wants to hold — each seq shard stores/attends seq/N locally
    # and the position table covers the GLOBAL length
    "bert-base-long": BertConfig(max_position=2048),
    "bert-tiny-long": BertConfig(hidden_size=128, num_layers=2, num_heads=2,
                                 intermediate_size=512, max_position=512),
    # one chip's share of A.X-K1 when 16 chips share each layer (experts 16
    # ways: 12 held; the vocabulary 8 ways comes from the tokenizer): every
    # width as published, the dense layer and 5 of the 60 expert layers
    "ax-k1-ep16-share": LatentMoEConfig(num_layers=6, experts_held=12,
                                        vocab_size=20_480),
    # the same share cut to the dense layer and ONE expert layer: what
    # chip_smoke.py builds, so that the standing proof is quick
    "ax-k1-ep16-share-l2": LatentMoEConfig(num_layers=2, experts_held=12,
                                           vocab_size=20_480),
    # the same family at a size the CPU tests run: 1 dense + 2 expert
    # layers, 8 experts in 4 groups of which 2 groups and 3 experts a token
    # are taken and 4 experts held
    "ax-k1-share-tiny": LatentMoEConfig(
        vocab_size=1000, hidden_size=128, num_layers=3, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=256,
        moe_intermediate_size=64, n_routed_experts=8, experts_held=4,
        num_experts_per_tok=3, n_group=4, topk_group=2, max_position=4096),
    # one pipeline STAGE of Xing4.0-29B-A4B (ep_size 1: each layer whole on
    # one chip, all 64 experts held): every width as published, the dense
    # layer (the two leading ones count once) and 5 of the 38 expert layers,
    # the whole vocabulary; a four-stream residual (hc_mult 4) and a
    # bias-corrected router
    "xing4-29b-ep1-stage": _xing4(num_layers=6, first_k_dense=1),
    # the same stage cut to the dense layer and ONE expert layer: what
    # chip_smoke.py builds
    "xing4-29b-ep1-stage-l2": _xing4(num_layers=2, first_k_dense=1),
    # the same family at a size the CPU tests run: 1 dense + 2 expert
    # layers, 8 experts all held of which 3 a token are taken, 4 streams
    "xing4-stage-tiny": _xing4(
        vocab_size=1000, hidden_size=128, num_layers=3, first_k_dense=1,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=256,
        moe_intermediate_size=64, n_routed_experts=8, experts_held=8,
        num_experts_per_tok=3, max_position=4096),
    # one chip's share of GLM-5.2 when 16 chips share each layer (experts 16
    # ways: 16 held; the vocabulary 8 ways: 19 360 rows): every width as
    # published, published layers 2-8 — the last dense layer (its indexer
    # scores), expert layers 3-5 (they use its picks), 6 (scores), 7 and 8
    "glm-5.2-ep16-share": _glm52(
        num_layers=7, first_k_dense=1, experts_held=16, vocab_size=19_360,
        indexer_types=_GLM52_HELD),
    # the same share cut to the dense layer and ONE expert layer (which uses
    # the dense layer's picks): what chip_smoke.py builds
    "glm-5.2-ep16-share-l2": _glm52(
        num_layers=2, first_k_dense=1, experts_held=16, vocab_size=19_360,
        indexer_types=_GLM52_HELD[:2]),
    # the same family at a size the CPU tests run: 1 dense + 4 expert layers
    # (full, shared, shared, full, shared), 4 heads of 24 + 8 / 32, an
    # indexer of 4 heads of 16 (8 rotated) that picks 16 positions, 8
    # experts of which 3 a token are taken and 4 held
    "glm52-share-tiny": _glm52(
        vocab_size=1000, hidden_size=128, num_layers=5, first_k_dense=1,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, intermediate_size=256,
        moe_intermediate_size=64, n_routed_experts=8, experts_held=4,
        num_experts_per_tok=3, max_position=4096, rope_original_max=4096,
        index_n_heads=4, index_head_dim=16, index_topk=16,
        indexer_types=("full", "shared", "shared", "full", "shared")),
    # one chip's share of Solar-Open2-250B when 16 chips share each layer
    # (experts 16 ways: 20 held; the vocabulary 8 ways comes from the
    # tokenizer): every width as published, two whole periods of the 12
    "solar-open2-ep16-share": HybridLinearConfig(
        num_layers=8, experts_held=20, vocab_size=24_576),
    # the same share cut to ONE period: what chip_smoke.py builds
    "solar-open2-ep16-share-l4": HybridLinearConfig(
        num_layers=4, experts_held=20, vocab_size=24_576),
    # the same family at a size the CPU tests run: 2 periods, 4 heads of 32
    # over 2 KV heads, 8 experts of which 3 a token are taken and 4 held
    "solar-open2-share-tiny": HybridLinearConfig(
        vocab_size=1000, hidden_size=128, num_layers=8, num_heads=4,
        num_kv_heads=2, head_dim=32, linear_num_heads=4, linear_head_dim=32,
        low_rank=16, moe_intermediate_size=64, n_routed_experts=8,
        experts_held=4, num_experts_per_tok=3, max_position=4096),
}


def get_config(name: str, vocab_size: Optional[int] = None,
               num_labels: Optional[int] = None, **overrides):
    """Look up a registered architecture, overriding data-dependent fields
    (vocab size comes from the corpus-built vocab at runtime).  An override
    a family has no field for (``num_labels`` or dropout on a served-only
    decoder) is left out: the callers pass one set for every family."""
    try:
        cfg = _REGISTRY[name]
    except KeyError:
        by_family = "; ".join(
            f"{fam}: {', '.join(names)}"
            for fam, names in sorted(models_by_family().items()))
        raise ValueError(
            f"unknown model {name!r}; use one of {by_family}") from None
    kw = dict(overrides)
    if vocab_size is not None:
        kw["vocab_size"] = vocab_size
    if num_labels is not None:
        kw["num_labels"] = num_labels
    fields = {f.name for f in dataclasses.fields(cfg)}
    kw = {k: v for k, v in kw.items() if k in fields}
    return cfg.replace(**kw) if kw else cfg


def available_models():
    return sorted(_REGISTRY)


def models_by_family() -> dict:
    out: dict = {}
    for name in available_models():
        out.setdefault(_REGISTRY[name].family, []).append(name)
    return out


def args_overrides(args) -> dict:
    """Config overrides an ``Args`` carries when explicitly set (None =
    keep the registry default) — shared by every ``get_config(args.model)``
    call site so CLI knobs can't silently apply on one path only."""
    kw = {}
    for f in ("moe_dispatch", "moe_capacity_factor", "moe_top_k",
              "moe_experts", "gelu"):
        v = getattr(args, f, None)
        if v is not None:
            kw[f] = v
    return kw
