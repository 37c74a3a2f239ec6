"""Multi-head scaled-dot-product attention + the impl routing policy.

The compute layout is TPU-first: batched einsums that XLA tiles straight
onto the MXU, softmax in fp32 regardless of the compute dtype (bf16 exponent
range is fine but the reduction wants fp32 mantissa), and an additive mask
bias instead of boolean select so the whole score pipeline stays fused.

``impl`` selects the kernel:

- ``"xla"`` — the always-correct reference path;
- ``"pallas"`` — the hand-written flash-attention kernel in
  ``pdnlp_tpu.ops.flash`` (segment-native: packed rows mask in-kernel from
  ``segment_ids`` instead of a [B, 1, S, S] HBM bias);
- ``"auto"`` — the default: pallas for SEGMENTED (packed) batches on a
  real TPU backend, where the quadratic segment-bias materialization is
  skipped; XLA otherwise (XLA's fused attention measured ahead of the
  dense-path kernel at every tested shape before PR 1 on v5e; record
  removed, not re-measured on this code).  A program that GSPMD partitions
  over several devices cannot hold a Mosaic kernel: there ``auto`` is
  pinned to XLA (:func:`pin_auto_for_mesh`).

Routing is resolved statically at trace time (:func:`routed_impl`); a
*requested* pallas that cannot run (sequence not tiling the 128-wide
kernel blocks) falls back to XLA with a once-per-process-per-shape warning
so a misrouted hot path is visible, not silent.  Attention-probability
dropout always forces XLA — the kernel does not implement it (documented;
the routing tests pin it).
"""
from __future__ import annotations

import functools
import sys
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # additive mask bias; well inside bf16/f32 range

#: Per-(width, segmented) routing crossovers consulted by ``"auto"`` —
#: full-step numbers (bert-base-long, fwd+bwd+AdamW) measured before PR 1 on
#: v5e; record removed, not re-measured on this code (the kernel was
#: rewritten since, and compiled for a chip for the first time in PR 21).
#: Dense (unsegmented) long widths then measured XLA ahead of the kernel at
#: every width, so auto keeps them on XLA even where the static rule would
#: allow pallas; segmented widths carry no entries — the static
#: packed-on-TPU-at-tiling-widths rule stands.  An entry here OVERRIDES the
#: static rule for auto only; explicit ``--attn_impl pallas``/``xla`` never
#: consults it.  Changing an entry is a measured change: it needs a chip cell.
ROUTING_TABLE = {
    (512, False): "xla",    # flash 0.66x full-step vs XLA (before PR 1)
    (1024, False): "xla",   # 0.73x
    (2048, False): "xla",   # 0.67x
}

#: shapes already warned about (once per process per shape, not per trace)
_FALLBACK_WARNED: set = set()


def mask_bias(attention_mask: jax.Array, dtype=jnp.float32) -> jax.Array:
    """[B, S] {0,1} mask -> [B, 1, 1, S] additive bias (0 keep / -1e9 drop)."""
    return ((1.0 - attention_mask.astype(jnp.float32)) * NEG_INF).astype(dtype)[
        :, None, None, :
    ]


def causal_bias(seq_len: int, dtype=jnp.float32) -> jax.Array:
    """[1, 1, S, S] additive causal bias (row i attends j <= i).

    This module is the SANCTIONED quadratic-mask site (jaxlint R14): the
    generative decoder's bucketed prefill composes this with the key-padding
    bias per forward, and the [S, S] term is a trace-time constant XLA
    folds — callers must route through here rather than build their own
    outer-product masks in hot paths.  The per-step decode path never needs
    it: a ``[rows, 1]`` query masks with the LINEAR visibility bias
    (``mask_bias`` over "position <= current"), which is what keeps decode
    free of quadratic work entirely."""
    i = jnp.arange(seq_len)
    keep = i[:, None] >= i[None, :]
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)[None, None]


def resolve_impl(requested: str, *, segmented: bool = False,
                 backend: Optional[str] = None) -> str:
    """Backend-level routing: ``"xla"``/``"pallas"`` pass through;
    ``"auto"`` becomes pallas for segmented (packed) batches on a real TPU
    backend and XLA everywhere else (the measured-faster choice — see the
    module docstring).  Shape/dropout feasibility is :func:`routed_impl`.
    ``backend`` overrides the running backend — how a CPU host reports the
    TPU routing policy without pretending to measure it."""
    if requested == "auto":
        backend = backend or jax.default_backend()
        return "pallas" if segmented and backend == "tpu" else "xla"
    if requested not in ("xla", "pallas"):
        raise ValueError(
            f"attention impl must be 'auto', 'xla' or 'pallas', "
            f"got {requested!r}")
    return requested


def pin_auto_for_mesh(requested: str, mesh, what: str = "attn_impl") -> str:
    """``requested`` as a program that GSPMD partitions over ``mesh`` may
    take it — for the attention kernel and the fused-CE kernel alike.

    Mosaic kernels cannot be partitioned automatically (the compiler's own
    refusal: "wrap the call in a shard_map"), so inside a ``jit`` whose
    arguments are sharded over more than one device ``auto`` means the XLA
    path, said once on stderr.  An explicit ``pallas`` passes through and
    fails at lowering with that message; the ``shard_map`` strategies
    (shardmap / sp / pp) run per-device bodies and never come here."""
    if requested != "auto" or mesh is None or mesh.size == 1:
        return requested
    key = ("mesh", what)
    if key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        print(f"[ops] {what}='auto' in a jit over a {mesh.size}-device mesh: "
              "Mosaic kernels cannot be partitioned automatically — taking "
              "the XLA path (a shard_map strategy keeps the kernels)",
              file=sys.stderr)
    return "xla"


def routed_impl(requested: str, seq_len: int, *, segmented: bool = False,
                dropout: bool = False, causal: bool = False,
                backend: Optional[str] = None) -> str:
    """The impl that will actually execute for this (static) configuration
    — the single decision :func:`dot_product_attention`, the trainer's
    ``step_dispatch`` span attr, and a run's report all share, so the
    surfaced impl can never drift from the routed one.

    ``"auto"`` first applies the backend-level rule (:func:`resolve_impl`)
    and then consults the measured per-(width, segmented) crossover table
    (:data:`ROUTING_TABLE`): a width the chip measured slower on the kernel
    routes to XLA with a once-per-shape "measured slower" warning —
    distinguishable from the "does not tile" fallback a pallas request
    takes below the 128-wide kernel blocks.  ``backend`` overrides the
    running backend (bench/test reporting from a CPU host)."""
    impl = resolve_impl(requested, segmented=segmented, backend=backend)
    if requested == "auto":
        measured = ROUTING_TABLE.get((int(seq_len), bool(segmented)))
        if measured == "xla":
            if impl == "pallas":  # the table OVERRODE the static rule
                _warn_fallback(requested, seq_len,
                               "measured slower than XLA at this width "
                               "(ROUTING_TABLE)")
            return "xla"
        if measured == "pallas":
            # a measured win routes pallas even where the static rule is
            # conservative (e.g. dense long widths after a kernel change)
            # — still TPU-only:
            # the kernel interprets (slowly) everywhere else
            bk = backend or jax.default_backend()
            impl = "pallas" if bk == "tpu" else "xla"
    if impl != "pallas":
        return "xla"
    if dropout:
        return "xla"  # kernel has no probability dropout (documented)
    if causal:
        # the flash kernel computes packed SEGMENT masks in-kernel but has
        # no causal tile term yet; causal attention (the generative
        # decoder's bucketed prefill) routes to XLA with the standard
        # once-per-shape warning so a future kernel causal variant shows
        # up as a routing change, not a silent drift.  The per-step decode
        # path ([rows, 1] queries) could never tile the kernel anyway.
        _warn_fallback(requested, seq_len,
                       "kernel has no causal mask term (generative prefill "
                       "runs XLA attention)")
        return "xla"
    from pdnlp_tpu.ops import flash

    if not flash.supported_seq(seq_len):
        _warn_fallback(requested, seq_len,
                       f"does not tile the {flash.BLOCK_Q}-wide kernel "
                       "blocks")
        return "xla"
    return "pallas"


@functools.lru_cache(maxsize=None)
def routed_impl_cached(requested: str, seq_len: int, *,
                       segmented: bool = False,
                       dropout: bool = False, causal: bool = False) -> str:
    """Memoized :func:`routed_impl` for per-dispatch host-loop callers
    (the trainer's and the serve engine's span stamping): routing is pure
    in its hashable arguments, so the hot loop pays one dict hit — the
    memoization lives HERE, next to the decision it wraps, not re-rolled
    per caller.  The fallback warning stays once-per-process either way."""
    return routed_impl(requested, seq_len, segmented=segmented,
                       dropout=dropout, causal=causal)


def _warn_fallback(requested: str, seq_len: int, reason: str) -> None:
    """Once per process per shape: a pallas-eligible attention routed to
    XLA — ``reason`` distinguishes "does not tile" (shape can never run
    the kernel) from "measured slower" (the crossover table overrode
    auto's static rule for this width)."""
    key = ("seq", seq_len, reason[:8])
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    print(f"[ops.attention] impl={requested!r} at seq_len={seq_len}: "
          f"{reason} — routing to XLA attention for this shape "
          "(widths from --length_buckets under 128 never tile; "
          "force --attn_impl xla|pallas to silence)", file=sys.stderr)


def dot_product_attention(
    q: jax.Array,  # [B, S, N, D]
    k: jax.Array,  # [B, S, N, D]
    v: jax.Array,  # [B, S, N, D]
    bias: Optional[jax.Array] = None,  # broadcastable to [B, N, Sq, Sk]
    impl: str = "auto",
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,  # [B, S] int, 0 = padding
    causal: bool = False,
) -> jax.Array:
    """Returns [B, S, N, D] attention output in q's dtype.

    ``dropout_rate`` > 0 (training only) drops attention *probabilities*,
    matching HF BERT's ``attention_probs_dropout_prob``.  The pallas kernel
    does not implement probability dropout, so a training-time dropout
    request always takes the XLA path.

    ``segment_ids`` carries the packed-row block-diagonal mask (attend iff
    query and key share a nonzero segment).  On the pallas path the mask is
    computed inside the kernel and the [B, 1, S, S] ``segment_bias`` never
    materializes; the XLA path builds it here (the retained reference
    fallback — ``data.packing.segment_bias``, hoisted by CSE under the
    default fully-unrolled layer scan).

    ``causal=True`` additionally masks row i from keys j > i
    (:func:`causal_bias`) — the generative decoder's prefill contract.  It
    COMPOSES with either a mask bias or ``segment_ids`` (a packed causal
    row: examples stay block-diagonal AND left-to-right within their
    segment), requires ``Sq == Sk`` (the per-step decode path carries its
    own linear visibility bias instead), and always routes XLA (the kernel
    has no causal term — :func:`routed_impl`).
    """
    if bias is not None and segment_ids is not None:
        # reject on EVERY route (the pallas kernel would raise; the XLA
        # path would silently apply only the bias and let co-packed
        # examples cross-attend — backend-dependent correctness)
        raise ValueError("pass bias OR segment_ids, not both — the packed "
                         "block-diagonal mask rides the IDs, and padding "
                         "is segment 0")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError(
            "causal=True needs Sq == Sk (a square trace-time mask); a "
            "decode-step query over a longer KV cache masks with its own "
            "linear visibility bias (mask_bias of 'position <= current')")
    use_dropout = dropout_rate > 0.0 and dropout_rng is not None
    impl = routed_impl(impl, q.shape[1], segmented=segment_ids is not None,
                       dropout=use_dropout, causal=causal)
    if impl == "pallas":
        from pdnlp_tpu.ops import flash

        return flash.flash_attention(q, k, v, bias, segment_ids=segment_ids)
    if segment_ids is not None and bias is None:
        from pdnlp_tpu.data.packing import segment_bias

        bias = segment_bias(segment_ids, dtype=jnp.float32).astype(q.dtype)
    if causal:
        cb = causal_bias(q.shape[1], jnp.float32)
        bias = cb if bias is None else bias.astype(jnp.float32) + cb
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if use_dropout:
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(dropout_rng, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0).astype(probs.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)
