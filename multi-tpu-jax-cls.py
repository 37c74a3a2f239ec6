"""Mesh data-parallel training — the DDP analog.

Capability twin of ``/root/reference/multi-gpu-distributed-cls.py``:
``dist.init_process_group`` -> ``jax.distributed`` rendezvous (env vars or
``--coordinator_address``); ``DistributedSampler`` -> per-host dataset shard
feeding one global device-sharded ``jax.Array``; DDP's NCCL gradient
all-reduce -> XLA ICI all-reduce inserted from sharding annotations; the
``loss_reduce``/``output_reduce`` collectives (``:139-155``) happen inside
the jitted step.  Steps per epoch shrink with the data axis (288 single ->
144 @ 2-way), matching the reference's step math.

Run (single host, all chips):   python multi-tpu-jax-cls.py
Multi-host (one process each):  python multi-tpu-jax-cls.py \
    --coordinator_address host0:8476 --num_processes 2 --process_id $RANK
The AMP-analog north-star config is ``--dtype bfloat16``.
"""
from pdnlp_tpu.train.run import run_parallel
from pdnlp_tpu.utils.config import Args, parse_cli

if __name__ == "__main__":
    run_parallel(parse_cli(base=Args(strategy="dp")), mode="dp")
