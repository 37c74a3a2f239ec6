#!/usr/bin/env python
"""Bench XLA vs Pallas flash attention on the real chip — fwd+bwd, bf16.

Two views:
  1. attention op alone at BERT-base head geometry across sequence lengths
     (tokens held ~constant so times are comparable);
  2. the full fused train step at seq 128 (the benchmark shape) and seq 512
     (the long-context shape), --attention_impl xla vs pallas.

    python scripts/bench_attention.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.ops.attention import dot_product_attention, mask_bias
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.steps import build_train_step, init_state
from pdnlp_tpu.utils.config import Args

N = 50
NHEADS, HDIM = 12, 64


def timeit(fn, *a):
    out = fn(*a)
    jax.block_until_ready(out)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]).astype(jnp.float32))
    t0 = time.time()
    for _ in range(N):
        out = fn(*a)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]).astype(jnp.float32))
    return (time.time() - t0) / N * 1e3


print("== attention op fwd+bwd (bf16, 12 heads x 64, ~131k tokens total) ==")
print(f"{'seq':>6} {'batch':>6} {'xla ms':>9} {'pallas ms':>10} {'speedup':>8}")
for S in (128, 256, 512, 1024, 2048):
    B = max(1, 4096 * 32 // (S))  # hold B*S ~ 131k tokens
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (B, S, NHEADS, HDIM), jnp.bfloat16)
               for i in range(3))
    bias = mask_bias(jnp.ones((B, S), jnp.int32), jnp.bfloat16)

    def loss(q, k, v, impl):
        return jnp.sum(dot_product_attention(q, k, v, bias, impl=impl)
                       .astype(jnp.float32))

    times = {}
    for impl in ("xla", "pallas"):
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)), static_argnums=3)
        times[impl] = timeit(g, q, k, v, impl)
    print(f"{S:>6} {B:>6} {times['xla']:>9.2f} {times['pallas']:>10.2f} "
          f"{times['xla']/times['pallas']:>8.2f}x")

print("\n== full fused train step (bert-base, bf16, fwd+bwd+AdamW) ==")
print(f"{'seq':>6} {'batch':>6} {'xla ms':>9} {'pallas ms':>10} {'speedup':>8}")
for S, B in ((128, 32), (512, 8), (1024, 4)):
    # attn_dropout=0: training-time probability dropout forces the XLA path
    # (ops.attention), so a pallas-vs-xla step comparison needs it off
    cfg = get_config("bert-base", vocab_size=16000, num_labels=6,
                     max_position=max(512, S), attn_dropout=0.0)
    key = jax.random.PRNGKey(0)
    params = bert.init_params(key, cfg)
    batch = jax.device_put({
        "input_ids": jnp.ones((B, S), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.ones((B, S), jnp.int32),
        "label": jnp.zeros((B,), jnp.int32),
        "example_weight": jnp.ones((B,), jnp.float32),
    })
    times = {}
    for impl in ("xla", "pallas"):
        args = Args(dtype="bfloat16", attention_impl=impl)
        tx = build_optimizer(params, args)
        state = init_state(key, cfg, tx, rng=jax.random.key(0, impl="rbg"),
                           params=params)
        step = jax.jit(build_train_step(cfg, tx, args))
        times[impl] = timeit(lambda: step(state, batch)[1]["loss"])
    print(f"{S:>6} {B:>6} {times['xla']:>9.2f} {times['pallas']:>10.2f} "
          f"{times['xla']/times['pallas']:>8.2f}x")
