"""Self-spawning multi-process launcher — the ``mp.spawn`` analog.

Capability twin of ``/root/reference/multi-gpu-distributed-mp-cls.py:361``:
one command forks ``--num_processes`` worker processes that rendezvous over
TCP (``init_method="tcp://localhost:12345"`` -> ``jax.distributed.initialize``
with a localhost coordinator) and run the same mesh-DP training as
``multi-tpu-jax-cls.py``.  The parent is only a process manager, exactly like
``mp.spawn``.

On a TPU pod each host instead runs one process (use multi-tpu-jax-cls.py
with ``--coordinator_address``); this single-command spawn flavor is for
multi-process runs on one machine and is exercised in CI on the CPU backend,
where each worker owns a slice of virtual devices.  A chip belongs to one
process, and this launcher does not partition a host's chips among workers:
``--num_processes > 1`` on a TPU backend is refused with one line rather than
left to hang in the second worker's start-up.  The parent itself never
initialises a backend — it reads ``jax.config`` (through ``parse_cli``) and
nothing else — so the workers find the devices free.

    python multi-tpu-spawn-cls.py --num_processes 2
"""
from __future__ import annotations

import os
import subprocess
import sys

from pdnlp_tpu.train.run import run_parallel
from pdnlp_tpu.utils.config import Args, parse_cli

# the tcp://localhost:12345 analog (different port: CI safety); the env
# override lets concurrent/back-to-back gangs avoid a lingering listener
# from a previously killed gang
_PORT = int(os.environ.get("PDNLP_SPAWN_PORT", "12355"))


def _worker_platform() -> str:
    """The platform a worker would get, found WITHOUT initialising a backend
    in this parent (which would hold the chip against its own workers):
    ``JAX_PLATFORMS`` where it names one, else a short-lived probe process
    that exits — and lets go of the device — before any worker starts."""
    named = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if named:
        return named.lower()
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        sys.exit("multi-tpu-spawn-cls.py: the backend probe failed:\n"
                 + probe.stderr[-2000:])
    return probe.stdout.split()[-1].lower()


def _launch_gang(args, extra_argv, num_processes=None) -> list:
    width = num_processes if num_processes is not None \
        else (args.num_processes or 1)
    procs = []
    for pid in range(width):
        env = dict(os.environ)
        env.update(
            COORDINATOR_ADDRESS=f"localhost:{_PORT}",
            NUM_PROCESSES=str(width),
            PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, __file__, *sys.argv[1:], *extra_argv], env=env))
    return procs


def spawn(args) -> int:
    """Fork ``num_processes`` copies of this script with PROCESS_ID set
    (the ``mp.spawn(main_worker, nprocs=N)`` analog).

    With ``--elastic true`` the parent becomes a degrade-don't-die gang
    supervisor (``parallel/watchdog.GangSupervisor`` — the capability the
    reference entirely lacks: a dead rank leaves its NCCL peers hung
    forever): workers heartbeat and snapshot full train state every
    ``--resume_every`` steps; if any child crashes or the stalest heartbeat
    exceeds ``--stall_timeout``, the parent kills the WHOLE gang (SPMD
    collectives cannot absorb a lone replacement rank), EVICTS ranks
    classified dead (``--elastic_shrink``, default on), and relaunches the
    survivors from the newest snapshot with capped exponential backoff and
    a restart budget.  A same-width restart is a bitwise continuation
    (resume restores params + Adam moments + step + RNG over the seeded
    data order, ``tests/test_resume.py``/``tests/test_elastic.py``); a
    reduced-width restart remaps the data position by epoch fraction and
    reshards state onto the surviving mesh (``tests/test_chaos.py``).
    """
    if not args.elastic:
        procs = _launch_gang(args, [])
        rc = 0
        for p in procs:
            rc = p.wait() or rc
        return rc

    import shutil

    from pdnlp_tpu.parallel.watchdog import GangSupervisor, heartbeat_dir
    from pdnlp_tpu.train import checkpoint as ckpt

    # A previous run's AUTO snapshot would make fresh workers "resume" at
    # its final step and train nothing — elastic state is per-run.  A
    # user-supplied --resume_from is the opposite intent (continue THAT
    # run) and is left strictly alone.
    if not args.resume_from or args.resume_from == "auto":
        ckpt.discard(args.resume_path())
        ckpt.discard(args.resume_path() + "-best")
        best_json = args.resume_path() + "-best.json"
        if os.path.exists(best_json):
            os.remove(best_json)
    shutil.rmtree(heartbeat_dir(args.output_dir), ignore_errors=True)

    worker_argv = ["--heartbeat_interval",
                   str(args.heartbeat_interval or 2.0),
                   "--resume_every", str(args.resume_every or 10)]
    if not args.resume_from:
        worker_argv += ["--resume_from", "auto"]

    def launch(width):
        # --num_processes last wins in argparse, and _launch_gang sets the
        # matching NUM_PROCESSES env — a shrunken gang rendezvouses at its
        # new world size and its workers rebuild mesh/loaders/shardings at
        # the surviving width (elastic-width resume remaps the rest)
        return _launch_gang(args, worker_argv + ["--num_processes",
                                                 str(width)],
                            num_processes=width)

    return GangSupervisor(
        launch, args.output_dir, args.num_processes or 1,
        stall_timeout=args.stall_timeout, max_restarts=args.max_restarts,
        shrink=args.elastic_shrink, min_processes=args.min_processes,
        backoff=args.restart_backoff, backoff_cap=args.restart_backoff_cap,
    ).run()


def main() -> int:
    args = parse_cli(base=Args(strategy="spawn"))
    already_child = os.environ.get("PROCESS_ID") is not None
    multi = bool(args.num_processes and args.num_processes > 1)
    # --elastic also supervises a WIDTH-1 gang: a single preemptible worker
    # still wants SIGKILL detection + restart-from-snapshot (and it is the
    # resume target a shrunken gang degrades to)
    if (multi or args.elastic) and not already_child \
            and args.process_id is None:
        if multi and _worker_platform() == "tpu":
            sys.exit(
                f"multi-tpu-spawn-cls.py: --num_processes "
                f"{args.num_processes} on a TPU backend: a chip belongs to "
                "one process and this launcher gives no worker its own. One "
                "process already drives every local chip "
                "(multi-tpu-jax-cls.py); across hosts run one process per "
                "host with --coordinator_address; for a multi-process "
                "rehearsal on one machine set JAX_PLATFORMS=cpu.")
        return spawn(args)
    # --mode picks the sharding the gang executes: dp (default, the
    # mp.spawn analog), zero (fully-sharded state spanning the process
    # boundary — the reference's actual DeepSpeed deployment shape,
    # multi-gpu-deepspeed-cls.py:299-302), tp/ep, pp (stage axis across
    # processes), or sp (ring attention's seq axis across processes).
    # Cross-process execution of zero/pp/tp/sp is pinned by
    # tests/test_spawn.py.
    if args.mode == "pp":
        from pdnlp_tpu.train.run import run_pipeline

        run_pipeline(args)
    elif args.mode == "sp":
        from pdnlp_tpu.train.run import run_sp

        run_sp(args)
    else:
        run_parallel(args, mode=args.mode)
    return 0


if __name__ == "__main__":
    sys.exit(main())
