#!/usr/bin/env python
"""Does unrolling the 12-layer lax.scan buy step time on the chip?"""
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
cfg = get_config(args.model, vocab_size=6013, num_labels=6)
key = jax.random.PRNGKey(0)
params = bert.init_params(key, cfg)
tx = build_optimizer(params, args)
state = init_state(key, cfg, tx, rng=jax.random.key(0, impl="rbg"),
                   params=params)
batch = jax.device_put({
    "input_ids": jnp.ones((B, S), jnp.int32),
    "token_type_ids": jnp.zeros((B, S), jnp.int32),
    "attention_mask": jnp.ones((B, S), jnp.int32),
    "label": jnp.zeros((B,), jnp.int32),
    "example_weight": jnp.ones((B,), jnp.float32),
})

def timeit(name, fn):
    out = fn()
    jax.block_until_ready(out)
    float(jnp.sum(out).astype(jnp.float32))
    t0 = time.time()
    for _ in range(N):
        out = fn()
    float(jnp.sum(out).astype(jnp.float32))
    print(f"{name:24s}: {(time.time()-t0)/N*1e3:7.2f} ms")


# scan_unroll=1 is the rolled scan, 12 == full unroll (also the None default)
for unroll in (1, 2, 4, 12):
    step = jax.jit(build_train_step(cfg, tx, args.replace(scan_unroll=unroll)))
    timeit(f"unroll={unroll}", lambda: step(state, batch)[1]["loss"])
