#!/usr/bin/env python
"""Measure dropout RNG cost: threefry vs rbg keys for the train step."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.steps import build_train_step, init_state
from pdnlp_tpu.utils.config import Args

N = 50
B, S = 32, 128

args = Args(strategy="dp", dtype="bfloat16")
cfg = get_config(args.model, vocab_size=16000, num_labels=6,
                 dropout=args.dropout, attn_dropout=args.attn_dropout)
key = jax.random.PRNGKey(0)
params = bert.init_params(key, cfg)
tx = build_optimizer(params, args)
batch = jax.device_put({
    "input_ids": jnp.ones((B, S), jnp.int32),
    "token_type_ids": jnp.zeros((B, S), jnp.int32),
    "attention_mask": jnp.ones((B, S), jnp.int32),
    "label": jnp.zeros((B,), jnp.int32),
    "example_weight": jnp.ones((B,), jnp.float32),
})


def timeit_step(name, step_fn, s):
    """Donated step (jaxlint R5): state threads through the loop — the
    input buffers are consumed each call, exactly like the real loop."""
    s, m = step_fn(s, batch)  # warmup/compile
    jax.block_until_ready(m["loss"])
    float(jnp.sum(m["loss"]).astype(jnp.float32))
    t0 = time.time()
    for _ in range(N):
        s, m = step_fn(s, batch)
    float(jnp.sum(m["loss"]).astype(jnp.float32))
    print(f"{name:30s}: {(time.time()-t0)/N*1e3:7.2f} ms")


step = jax.jit(build_train_step(cfg, tx, args), donate_argnums=0)
for impl in ("threefry2x32", "rbg", "unsafe_rbg"):
    # fresh params per impl: the donated step consumed the previous
    # incarnation's buffers
    state = init_state(key, cfg, tx, rng=jax.random.key(0, impl=impl),
                       params=bert.init_params(key, cfg))
    try:
        timeit_step(f"full step rng={impl}", step, state)
    except Exception as e:
        print(f"{impl}: FAILED {type(e).__name__}: {e}")
