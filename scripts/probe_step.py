#!/usr/bin/env python
"""Bisect the device step cost: which part of the 39ms/step is what."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.steps import build_train_step, init_state, weighted_ce
from pdnlp_tpu.utils.config import Args

N = 50
B, S = 32, 128

args = Args(strategy="dp", dtype="bfloat16")
cfg = get_config(args.model, vocab_size=16000, num_labels=6,
                 dropout=args.dropout, attn_dropout=args.attn_dropout)
key = jax.random.PRNGKey(0)
params = bert.init_params(key, cfg)
tx = build_optimizer(params, args)
state = init_state(key, cfg, tx, rng=jax.random.key(0), params=params)
batch = {
    "input_ids": jnp.ones((B, S), jnp.int32),
    "token_type_ids": jnp.zeros((B, S), jnp.int32),
    "attention_mask": jnp.ones((B, S), jnp.int32),
    "label": jnp.zeros((B,), jnp.int32),
    "example_weight": jnp.ones((B,), jnp.float32),
}
batch = jax.device_put(batch)


def timeit(name, fn, *a, donated=False):
    # warmup/compile
    out = fn(*a)
    jax.block_until_ready(out)
    sync = jax.tree_util.tree_leaves(out)[0]
    float(jnp.sum(sync).astype(jnp.float32))
    t0 = time.time()
    for _ in range(N):
        out = fn(*a)
    sync = jax.tree_util.tree_leaves(out)[0]
    float(jnp.sum(sync).astype(jnp.float32))
    dt = (time.time() - t0) / N * 1e3
    print(f"{name:34s}: {dt:7.2f} ms")
    return dt


def timeit_step(name, step_fn, cfg_for_state):
    """Time a DONATED full train step the way the real loop runs it:
    state threads through each iteration (jaxlint R5 — donation keeps the
    step at 1x state HBM instead of a transient 2x).  The step consumes
    its input buffers, so it gets a PRIVATE state on fresh params — the
    shared probe `state`/`params` above stay live for the forward-only
    and optimizer-only sections."""
    s = init_state(key, cfg_for_state, tx, rng=jax.random.key(0),
                   params=bert.init_params(key, cfg_for_state))
    s, m = step_fn(s, batch)  # warmup/compile
    jax.block_until_ready(m["loss"])
    float(jnp.sum(m["loss"]).astype(jnp.float32))
    t0 = time.time()
    for _ in range(N):
        s, m = step_fn(s, batch)
    float(jnp.sum(m["loss"]).astype(jnp.float32))
    dt = (time.time() - t0) / N * 1e3
    print(f"{name:34s}: {dt:7.2f} ms")
    return dt


# 1. full train step (the benched program), donated + state-threaded
full = jax.jit(build_train_step(cfg, tx, args), donate_argnums=0)
timeit_step("full step (dropout on)", full, cfg)

# 2. no-dropout variant
cfg_nd = get_config(args.model, vocab_size=16000, num_labels=6,
                    dropout=0.0, attn_dropout=0.0)
full_nd = jax.jit(build_train_step(cfg_nd, tx, args), donate_argnums=0)
timeit_step("full step (dropout off)", full_nd, cfg_nd)

dtype = jnp.bfloat16

# 3. forward only (train mode, dropout on)
def fwd(params, batch, rng):
    logits = bert.classify(params, cfg, batch, dtype=dtype, deterministic=False,
                           rng=rng)
    return weighted_ce(logits, batch["label"], batch["example_weight"])[0]

fwd_j = jax.jit(fwd)
rng = jax.random.key(1)
timeit("forward only (dropout on)", lambda: fwd_j(state["params"], batch, rng))

def fwd_det(params, batch):
    logits = bert.classify(params, cfg, batch, dtype=dtype, deterministic=True)
    return weighted_ce(logits, batch["label"], batch["example_weight"])[0]

fwd_det_j = jax.jit(fwd_det)
timeit("forward only (deterministic)", lambda: fwd_det_j(state["params"], batch))

# 4. fwd+bwd, no optimizer
grad_j = jax.jit(jax.grad(fwd))
timeit("fwd+bwd (dropout on)", lambda: grad_j(state["params"], batch, rng))

grad_det_j = jax.jit(jax.grad(fwd_det))
timeit("fwd+bwd (deterministic)", lambda: grad_det_j(state["params"], batch))

# 5. optimizer only
grads = grad_j(state["params"], batch, rng)
grads = jax.block_until_ready(grads)

def opt_only(g, opt_state, params):
    updates, opt_state = tx.update(g, opt_state, params)
    return optax.apply_updates(params, updates)

opt_j = jax.jit(opt_only)
timeit("AdamW update only", lambda: opt_j(grads, state["opt_state"], state["params"]))

# 6. pallas attention variant
args_p = args.replace(attention_impl="pallas")
full_p = jax.jit(build_train_step(cfg, tx, args_p), donate_argnums=0)
timeit_step("full step (pallas attn, dropout on)", full_p, cfg)

args_pn = args_p
full_pn = jax.jit(build_train_step(cfg_nd, tx, args_pn), donate_argnums=0)
timeit_step("full step (pallas, dropout off)", full_pn, cfg_nd)
