#!/usr/bin/env python
"""Probe: optimizer state in pinned host memory (DeepSpeed cpu-offload
analog) — does XLA's TPU host-memory space work here, and at what cost?"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.parallel import make_mesh
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.steps import build_train_step, init_state
from pdnlp_tpu.utils.config import Args

N = 30
B, S = 32, 128

args = Args(strategy="dp", dtype="bfloat16")
mesh = make_mesh()
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

dev_sh = NamedSharding(mesh, P())
host_sh = NamedSharding(mesh, P(), memory_kind="pinned_host")


def shardings_of(state, opt_kind):
    def walk(tree, sh):
        return jax.tree_util.tree_map(lambda _: sh, tree)

    return {
        "params": walk(state["params"], dev_sh),
        "opt_state": walk(state["opt_state"], opt_kind),
        "step": dev_sh,
        "rng": dev_sh,
    }


def timeit(name, step, st):
    st, m = step(st, batch)
    float(jax.device_get(m["loss"]))
    t0 = time.time()
    for _ in range(N):
        st, m = step(st, batch)
    float(jax.device_get(m["loss"]))
    print(f"{name:28s}: {(time.time()-t0)/N*1e3:7.2f} ms/step")
    return st


import optax

from pdnlp_tpu.models import bert as bert_mod
from pdnlp_tpu.train.precision import resolve_dtype
from pdnlp_tpu.train.steps import weighted_ce


def build_offload_step():
    """Train step with explicit host<->device staging of optimizer state
    (the DeepSpeed cpu-offload pattern: moments live in host RAM)."""
    dtype = resolve_dtype(args.dtype)

    def loss_fn(params, batch, rng):
        logits = bert_mod.classify(params, cfg, batch, dtype=dtype,
                                   deterministic=False, rng=rng)
        return weighted_ce(logits, batch["label"], batch["example_weight"])[0]

    def step(state, batch):
        rng = jax.random.fold_in(state["rng"], state["step"])
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch, rng)
        opt_dev = jax.device_put(state["opt_state"], dev_sh)      # host->dev
        updates, opt_dev = tx.update(grads, opt_dev, state["params"])
        params = optax.apply_updates(state["params"], updates)
        opt_host = jax.device_put(opt_dev, host_sh)               # dev->host
        return ({"params": params, "opt_state": opt_host,
                 "step": state["step"] + 1, "rng": state["rng"]},
                {"loss": loss})

    return step


fn = build_train_step(cfg, tx, args)
for name, kind in (("opt state on device", dev_sh),
                   ("opt state in pinned host", host_sh)):
    try:
        sh = shardings_of(state, kind)
        # fresh buffers: device_put with an identical sharding aliases the
        # input, and the donating step below would delete the original
        st = jax.device_put(jax.tree_util.tree_map(jnp.copy, state), sh)
        body = fn if kind is dev_sh else build_offload_step()
        step = jax.jit(body, donate_argnums=0, in_shardings=(sh, dev_sh),
                       out_shardings=(sh, dev_sh))
        timeit(name, step, st)
    except Exception as e:
        print(f"{name}: FAILED {type(e).__name__}: {str(e)[:300]}")
