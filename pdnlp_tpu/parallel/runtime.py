"""Multi-process runtime init — the rendezvous layer.

Twin of the reference's two rendezvous modes: env-var
``dist.init_process_group`` (``/root/reference/multi-gpu-distributed-cls.py:
275-284``) and explicit TCP (``multi-gpu-distributed-mp-cls.py:265-266``).
JAX collapses both into ``jax.distributed.initialize(coordinator, n, id)``;
afterwards every process sees the global device set and ``jit`` programs are
single-program-multiple-data across hosts (DCN for cross-host, ICI within).
"""
from __future__ import annotations

import os
from typing import Tuple


def init_runtime(args) -> Tuple[int, int]:
    """Initialize multi-process JAX if configured; returns
    ``(process_index, process_count)``.

    Config precedence: explicit ``Args`` fields, then the standard env vars
    (``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID`` — the
    MASTER_ADDR/WORLD_SIZE/RANK analog), else single-process.

    Idempotent: entrypoints may call it early (e.g. to resolve a default
    mesh from the device count) and again inside the shared runner —
    ``jax.distributed.initialize`` itself raises on a second call.

    Platform and virtual-device selection are JAX's own: ``JAX_PLATFORMS``
    and ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` are read from
    the environment at backend init, so nothing is re-applied here.
    """
    import jax

    coord = args.coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    nproc = args.num_processes or _int_env("NUM_PROCESSES")
    pid = args.process_id if args.process_id is not None else _int_env("PROCESS_ID")

    if coord and nproc and nproc > 1 \
            and not jax.distributed.is_initialized():
        # NOTE: checked via the distributed client, not process_count() —
        # the latter would initialize the backend, which must not happen
        # before the distributed client is up
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(pid or 0),
        )
    return jax.process_index(), jax.process_count()


def _int_env(name: str):
    v = os.environ.get(name)
    return int(v) if v else None
