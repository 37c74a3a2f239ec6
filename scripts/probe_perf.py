#!/usr/bin/env python
"""Perf probe: isolate device step time vs host/data/transfer time.

Times three loops over N steps of the benched config (dp, bf16, batch 32):
  a) device-only: one pre-transferred batch re-fed every step;
  b) +transfer:   one pre-collated host batch, put() every step;
  c) full loop:   real loader (cached encodings) + put() every step.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from pdnlp_tpu.utils.config import enable_compilation_cache

enable_compilation_cache()

from pdnlp_tpu.train.run import build_parallel_trainer
from pdnlp_tpu.utils.config import Args

N = 100

args = Args(strategy="dp", dtype="bfloat16", dev=True, log_every=10**9)
trainer, train_loader, dev_loader = build_parallel_trainer(args, mode="dp")
host_batch = next(iter(train_loader))
dev_batch = trainer.put(host_batch)
trainer.train_step.lower(trainer.state, dev_batch).compile()

def finish(metrics):
    float(jax.device_get(metrics["loss"]))

# warmup
state = trainer.state
for _ in range(3):
    state, m = trainer.train_step(state, dev_batch)
finish(m)

t0 = time.time()
for _ in range(N):
    state, m = trainer.train_step(state, dev_batch)
finish(m)
t_dev = time.time() - t0

t0 = time.time()
for _ in range(N):
    state, m = trainer.train_step(state, trainer.put(host_batch))
finish(m)
t_put = time.time() - t0

t0 = time.time()
it = iter(train_loader)
n_full = 0
for batch in it:
    state, m = trainer.train_step(state, trainer.put(batch))
    n_full += 1
    if n_full == N:
        break
finish(m)
t_full = time.time() - t0

# dispatch-only cost: how long does enqueueing N steps take (no barrier)?
t0 = time.time()
for _ in range(N):
    state, m = trainer.train_step(state, dev_batch)
t_enq = time.time() - t0  # jaxlint: disable=R4 — the no-barrier delta IS the measurement here
finish(m)

flops_step = 6 * 85.6e6 * (32 * 128) + 12 * 2 * 2 * 32 * 12 * 128 * 128 * 64 * 3
print(f"device-only : {t_dev/N*1e3:8.2f} ms/step  ({N/t_dev:6.1f} steps/s)")
print(f"+put()      : {t_put/N*1e3:8.2f} ms/step  ({N/t_put:6.1f} steps/s)")
print(f"full loader : {t_full/n_full*1e3:8.2f} ms/step  ({n_full/t_full:6.1f} steps/s)")
print(f"enqueue-only: {t_enq/N*1e3:8.2f} ms/step (host dispatch cost)")
print(f"approx MFU at device-only: {flops_step/(t_dev/N)/197e12*100:.1f}% (v5e bf16 peak 197 TF/s)")
