"""Failure detection + elastic restart — chaos test with real processes.

The reference has no failure handling (``SURVEY.md`` §5): a dead rank hangs
its NCCL peers forever.  Here the spawn launcher is also a failure detector
(``parallel/watchdog.py``): workers heartbeat + snapshot full train state
periodically; the parent kills and relaunches the whole gang from the newest
snapshot on a crash or stall.  The acceptance bar is the strongest one the
framework's bitwise-resume contract allows: a run whose rank is KILLED
mid-training must end with byte-identical (``array_equal``) parameters to an
undisturbed run of the IDENTICAL 2-process x 4-device layout — same
programs, same collective reassociation, so exact equality is the honest
assert.  A cross-layout comparison (8-device single process) is additionally
pinned to float tolerance, where reassociated reductions legitimately
differ in the last bits.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMMON_ARGS = [
    "--model", "bert-tiny", "--data_limit", "600", "--max_seq_len", "32",
    "--train_batch_size", "4", "--dtype", "float32",
    "--dropout", "0.0", "--attn_dropout", "0.0",  # determinism across layouts
    "--epochs", "1",
]


@pytest.fixture(scope="module")
def elastic_run(tmp_path_factory, corpus_cli):
    """Elastic spawn (2 procs x 4 CPU devices) with rank 1 chaos-killed at
    step 8; snapshots every 3 steps -> the restart resumes from step 6."""
    out = tmp_path_factory.mktemp("elastic")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PDNLP_FAULT_STEP="8",
        PDNLP_FAULT_PROC="1",
        # own rendezvous port: the suite's other gang fixtures run beside
        # this one under xdist (tests/test_spawn.py holds the default)
        PDNLP_SPAWN_PORT="12393",
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--output_dir", str(out),
         "--elastic", "true", "--resume_every", "3", "--stall_timeout", "60",
         # this module pins the BYTE-IDENTICAL same-layout contract, so the
         # restart must keep the 2x4 layout: opt out of the default
         # evict-and-shrink policy (tests/test_chaos.py covers eviction)
         "--elastic_shrink", "false",
         *COMMON_ARGS, *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200,
    )
    return proc, out


class FakeClock:
    """Injected time source: the stall thresholds are exact comparisons
    against this, never against real sleeps — deterministic under any CPU
    contention (the old real-sleep version flaked in tier-1)."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeProc:
    def __init__(self, code=None):
        self.code = code

    def poll(self):
        return self.code


def test_gang_monitor_stall_detection(tmp_path):
    """The stall detector (no crash, heartbeats stop) — unit-level, no
    processes, no sleeps: both sides run on one injected clock, so the
    timeout arithmetic is exact."""
    from pdnlp_tpu.parallel.watchdog import GangMonitor, Heartbeat

    clk = FakeClock()
    procs = [FakeProc(), FakeProc()]
    mon = GangMonitor(procs, str(tmp_path), 2, stall_timeout=30.0,
                      clock=clk)
    # no rank has ever beaten: grace period, healthy
    assert mon.poll() is None
    # both beat now -> healthy
    hb0 = Heartbeat(str(tmp_path), 0, interval=0.0, clock=clk)
    hb1 = Heartbeat(str(tmp_path), 1, interval=0.0, clock=clk)
    clk.advance(1.0)
    hb0.beat(force=True, step=4)
    hb1.beat(force=True, step=4)
    assert mon.poll() is None
    # rank 1 goes quiet past the timeout while rank 0 keeps beating
    clk.advance(31.0)
    hb0.beat(force=True, step=40)
    v = mon.poll()
    assert v is not None and v["kind"] == "stalled", v
    assert v["stalest_beat_s"] == 31.0
    # the verdict carries the gang's LAGGARD progress metadata: the monitor
    # can tell "slow but advancing" from "dead at step 4"
    assert v["last_step"] == 4
    # a nonzero child exit is classified as a crash (takes precedence)
    procs[1].code = 13
    assert mon.poll()["kind"] == "crashed"
    # all children exiting 0 ends the run
    procs[0].code = procs[1].code = 0
    assert mon.poll()["kind"] == "done"


def test_gang_monitor_startup_stall_without_any_beat(tmp_path):
    """Rendezvous deadlock shape: nobody ever beats — stall after the 4x
    pre-first-beat grace window (exact, on the injected clock)."""
    from pdnlp_tpu.parallel.watchdog import GangMonitor

    clk = FakeClock()
    mon = GangMonitor([FakeProc()], str(tmp_path), 1, stall_timeout=30.0,
                      clock=clk)
    clk.advance(4 * 30.0)
    assert mon.poll() is None  # boundary: strictly-greater fires the stall
    clk.advance(0.5)
    v = mon.poll()
    assert v is not None and v["kind"] == "stalled"
    assert v["stalest_beat_s"] is None


def test_heartbeat_payload_and_monitor_status(tmp_path):
    """The beat file carries step metadata; the monitor surfaces it in its
    status line and derives steps/s from consecutive beats when the worker
    does not supply a smoothed rate."""
    from pdnlp_tpu.parallel.watchdog import GangMonitor, Heartbeat

    clk = FakeClock()
    mon = GangMonitor([FakeProc()], str(tmp_path), 1, stall_timeout=30.0,
                      clock=clk)
    hb = Heartbeat(str(tmp_path), 0, interval=0.0, clock=clk)
    clk.advance(1.0)
    hb.beat(force=True, step=10)
    clk.advance(5.0)
    hb.beat(force=True, step=20)  # 10 steps / 5 s -> derived rate 2.0
    s = mon.status()
    assert s["last_step"] == 20
    assert s["steps_per_sec"] == 2.0
    assert s["stalest_beat_s"] == 0.0
    line = mon.status_line()
    assert "step 20" in line and "2.0 steps/s" in line
    # an explicitly supplied smoothed rate (the obs regression detector's)
    # wins over the derived one
    clk.advance(1.0)
    hb.beat(force=True, step=22, steps_per_sec=3.5)
    assert mon.status()["steps_per_sec"] == 3.5


def test_elastic_restart_completes(elastic_run):
    proc, out = elastic_run
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    # the parent detected the crash and restarted the gang exactly once
    assert "[elastic] gang failure" in proc.stderr
    assert "restart 1/" in proc.stderr
    # the restarted gang resumed from a snapshot, not from scratch
    assert re.search(r"resumed from .*resume-spawn\.msgpack at step [1-9]",
                     proc.stdout), proc.stdout[-2000:]
    assert (out / "spawn-cls.msgpack").exists()


@pytest.fixture(scope="module")
def undisturbed_run(tmp_path_factory, corpus_cli):
    """The SAME 2-proc x 4-device spawn configuration with no chaos hook —
    the layout-matched control for the byte-identical assert."""
    out = tmp_path_factory.mktemp("undisturbed")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        # own rendezvous port: the elastic fixture's killed gang may leave
        # a worker lingering on the default one
        PDNLP_SPAWN_PORT="12391",
    )
    for k in ("COORDINATOR_ADDRESS", "PROCESS_ID",
              "PDNLP_FAULT_STEP", "PDNLP_FAULT_PROC"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--output_dir", str(out), *COMMON_ARGS,
         *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc, out


def _flat_raw(path):
    """(structure, concatenated leaves) of a raw msgpack checkpoint — no
    model template needed for an exact-bytes comparison."""
    import flax.serialization as ser
    import jax

    with open(str(path), "rb") as f:
        tree = ser.msgpack_restore(f.read())
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, np.concatenate([np.ravel(l) for l in leaves])


def test_elastic_params_byte_identical_to_undisturbed_run(
        elastic_run, undisturbed_run):
    """Crash + gang restart + bitwise resume == a run with no failure,
    byte for byte: both runs use the identical 2x4 spawn layout, so the
    programs (and their collective reassociation) are the same and
    ``array_equal`` is the justified assert."""
    proc, out = elastic_run
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    uproc, uout = undisturbed_run
    assert uproc.returncode == 0, (uproc.stdout[-2000:], uproc.stderr[-3000:])

    def_e, flat_elastic = _flat_raw(out / "spawn-cls.msgpack")
    def_c, flat_clean = _flat_raw(uout / "spawn-cls.msgpack")
    assert def_e == def_c
    assert np.array_equal(flat_elastic, flat_clean), (
        f"{(flat_elastic != flat_clean).sum()} of {flat_elastic.size} leaves"
        f" differ; max abs diff {np.abs(flat_elastic - flat_clean).max()}")


def test_elastic_params_match_single_process_run(elastic_run, ndev,
                                                 corpus_files):
    """Cross-LAYOUT parity (2x4 spawn vs 8-device in-process): collective
    reassociation differs between layouts, so this is a float-tolerance
    check, not the byte-identical contract (which
    ``test_elastic_params_byte_identical_to_undisturbed_run`` pins against
    the layout-matched control)."""
    proc, out = elastic_run
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])

    import jax

    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="spawn", model="bert-tiny", data_limit=600,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1,
                output_dir=str(out), log_every=10 ** 9, **corpus_files)
    trainer, train_loader, _ = build_parallel_trainer(args, mode="dp")
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))

    restored = ckpt.load_params(str(out / "spawn-cls.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)
