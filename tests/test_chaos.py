"""Preemption-grade resilience: eviction, elastic width, async publishing.

The acceptance bar of ROADMAP item 4, in three layers:

- **unit** — the async checkpointer's never-block/at-most-one-in-flight
  contract, crash-atomic publish + manifest verification + previous-
  snapshot fallback, and the supervisor's evict/backoff/budget policy on
  fake processes;
- **in-process** — a ZeRO-sharded run snapshotted at width 8 resumes at
  width 4: consolidate-then-reshard of params AND Adam moments, the
  sampler's row assignment recomputed, and the step counter remapped by
  epoch fraction;
- **chaos (real processes)** — a worker SIGKILLed mid-epoch (the
  preemption shape: no flush, no teardown, peers wedged in collectives)
  leads to supervisor eviction and a completed run at reduced width; the
  same-width variant (``--elastic_shrink false``) must reproduce the
  undisturbed run's golden per-step loss trace after restart.
"""
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from pdnlp_tpu.train import checkpoint as ckpt  # noqa: E402
from pdnlp_tpu.train.async_ckpt import AsyncCheckpointer  # noqa: E402

from tests.test_elastic import FakeClock, FakeProc  # noqa: E402


# ----------------------------------------------------------- async publisher

def test_async_checkpointer_never_blocks_and_publishes(tmp_path, monkeypatch):
    """submit() returns while the publish is gated; at most one save is in
    flight; a same-path re-submit supersedes the queued snapshot; wait()
    drains and the published file passes manifest verification."""
    gate = threading.Event()
    entered = threading.Event()
    concurrent = []
    real_publish = ckpt.publish

    def gated_publish(path, data, meta=None):
        concurrent.append(1)
        assert sum(concurrent) == 1, "more than one save in flight"
        entered.set()
        assert gate.wait(10)
        try:
            real_publish(path, data, meta=meta)
        finally:
            concurrent.pop()

    monkeypatch.setattr(ckpt, "publish", gated_publish)
    w = AsyncCheckpointer(process_index=0)
    path = str(tmp_path / "snap.msgpack")
    w.submit(path, {"x": np.ones(4)}, meta={"step": 1})
    assert entered.wait(10)
    # the writer is parked inside publish: the step loop is NOT
    assert not os.path.exists(path)
    # two more submits for the same path: the queued one is superseded
    w.submit(path, {"x": np.full(4, 2.0)}, meta={"step": 2})
    w.submit(path, {"x": np.full(4, 3.0)}, meta={"step": 3})
    assert w.stats()["superseded"] == 1
    gate.set()
    assert w.wait(timeout=30)
    assert w.stats()["published"] == 2  # step-1 and the surviving step-3
    ok, reason = ckpt.verify(path)
    assert ok, reason
    assert ckpt.load_manifest(path)["meta"] == {"step": 3}
    raw = ckpt.load_raw(path)
    np.testing.assert_array_equal(raw["x"], np.full(4, 3.0))


def test_async_checkpointer_surfaces_write_errors(tmp_path, monkeypatch):
    def broken_publish(path, data, meta=None):
        raise OSError("disk on fire")

    monkeypatch.setattr(ckpt, "publish", broken_publish)
    w = AsyncCheckpointer(process_index=0)
    w.submit(str(tmp_path / "a.msgpack"), {"x": np.ones(2)})
    deadline = time.time() + 10
    while not w.stats()["errors"] and time.time() < deadline:
        time.sleep(0.01)
    # loud on the NEXT save, not at the end of the run
    with pytest.raises(RuntimeError, match="async checkpoint publish"):
        w.submit(str(tmp_path / "b.msgpack"), {"x": np.ones(2)})


def test_async_checkpointer_nonzero_rank_never_writes(tmp_path):
    w = AsyncCheckpointer(process_index=1)
    w.submit(str(tmp_path / "r1.msgpack"), {"x": np.ones(2)})
    assert w.wait(timeout=5)
    assert not os.path.exists(tmp_path / "r1.msgpack")
    assert w.stats()["submitted"] == 0


# ------------------------------------------- crash-atomic publish + fallback

def test_corrupt_checkpoint_falls_back_to_previous_snapshot(tmp_path, capfd):
    path = str(tmp_path / "state.msgpack")
    ckpt.save(path, {"w": np.arange(6, dtype=np.float32)}, meta={"step": 2})
    ckpt.save(path, {"w": np.arange(6, dtype=np.float32) * 10},
              meta={"step": 4})
    # truncate the newest published file (host crash before the page cache
    # drained): load must verify the manifest, warn LOUDLY, and serve the
    # retained previous snapshot instead of crashing
    with open(path, "r+b") as f:
        f.truncate(8)
    restored = ckpt.load(path, {"w": np.zeros(6, dtype=np.float32)})
    np.testing.assert_array_equal(restored["w"],
                                  np.arange(6, dtype=np.float32))
    assert "falling back" in capfd.readouterr().err
    # no previous snapshot -> the corruption is a loud error, not a guess
    lone = str(tmp_path / "lone.msgpack")
    ckpt.save(lone, {"w": np.ones(3)})
    with open(lone, "r+b") as f:
        f.truncate(4)
    with pytest.raises(ckpt.CorruptCheckpointError, match="manifest"):
        ckpt.load(lone, {"w": np.zeros(3)})


def test_corrupt_manifest_json_routes_to_fallback_not_crash(tmp_path):
    """A bit-rotted MANIFEST (undecodable JSON) is corruption too: verify
    must report it, and load must fall back to .prev — not crash with a
    raw json error."""
    path = str(tmp_path / "mrot.msgpack")
    ckpt.save(path, {"w": np.zeros(4, dtype=np.float32)})
    ckpt.save(path, {"w": np.ones(4, dtype=np.float32)})  # .prev retained
    with open(ckpt.manifest_path(path), "w") as f:
        f.write("{not json")
    ok, reason = ckpt.verify(path)
    assert not ok and "manifest" in reason
    restored = ckpt.load(path, {"w": np.zeros(4, dtype=np.float32)})
    np.testing.assert_array_equal(restored["w"], np.zeros(4))


def test_torn_publish_never_destroys_the_good_prev(tmp_path, monkeypatch):
    """Crash #1 between data and manifest leaves path corrupt; the NEXT
    publish must not retain that corrupt pair over the good .prev — a
    second torn crash would otherwise leave zero loadable snapshots."""
    path = str(tmp_path / "torn.msgpack")
    ckpt.save(path, {"w": np.zeros(4, dtype=np.float32)})  # v1 (good)
    # v2 publish crashes after the data replace, before the manifest:
    # simulate by writing new bytes under the v1 manifest — and clear the
    # publisher's in-process CRC cache, because a torn publish only exists
    # across a process death (the restarted process trusts nothing)
    from flax import serialization

    with open(path, "wb") as f:
        f.write(serialization.to_bytes({"w": np.ones(4, dtype=np.float32)}))
    ckpt._published_crc.clear()
    assert not ckpt.verify(path)[0]
    assert not os.path.exists(ckpt.prev_path(path))  # no prev yet
    # v3 publish: must NOT retain the torn pair as .prev
    ckpt.save(path, {"w": np.full(4, 3.0, dtype=np.float32)})
    assert ckpt.verify(path)[0]
    assert not os.path.exists(ckpt.prev_path(path))
    # ...whereas publishing over the now-GOOD v3 retains it normally
    ckpt.save(path, {"w": np.full(4, 4.0, dtype=np.float32)})
    assert ckpt.verify(ckpt.prev_path(path))[0]


def test_checksum_mismatch_detected_not_just_truncation(tmp_path):
    path = str(tmp_path / "flip.msgpack")
    ckpt.save(path, {"w": np.zeros(64, dtype=np.float32)})
    with open(path, "r+b") as f:  # same length, flipped bytes
        f.seek(32)
        f.write(b"\xff\xff")
    ok, reason = ckpt.verify(path)
    assert not ok and "crc32" in reason


def test_shape_mismatch_is_not_corruption(tmp_path):
    """A template mismatch must raise ValueError (wrong model), never fall
    back to .prev — an older snapshot of the wrong model is just as wrong."""
    path = str(tmp_path / "tmpl.msgpack")
    ckpt.save(path, {"w": np.zeros(4)})
    ckpt.save(path, {"w": np.ones(4)})  # .prev now exists
    with pytest.raises(ValueError, match="does not match"):
        ckpt.load(path, {"w": np.zeros(8)})


# ------------------------------------------------------- supervisor (policy)

class KillableProc(FakeProc):
    """FakeProc that honors the supervisor's kill_gang teardown."""

    def terminate(self):
        self.code = -15

    def kill(self):
        self.code = -9


class ScriptedLaunch:
    """launch(width) returning scripted FakeProc gangs, recording widths."""

    def __init__(self, outcomes):
        # one entry per incarnation: "crash<rank>" or "done"
        self.outcomes = list(outcomes)
        self.widths = []

    def __call__(self, width):
        self.widths.append(width)
        outcome = self.outcomes.pop(0)
        if outcome == "done":
            return [KillableProc(0) for _ in range(width)]
        rank = int(outcome.removeprefix("crash"))
        return [KillableProc(13 if i == rank else None)
                for i in range(width)]


def _supervisor(launch, tmp_path, n, **kw):
    from pdnlp_tpu.parallel.watchdog import GangSupervisor

    clk = FakeClock()
    sleeps = []

    def sleep(s):  # injected sleeps advance the injected clock
        sleeps.append(s)
        clk.advance(s)

    sup = GangSupervisor(launch, str(tmp_path), n, stall_timeout=30.0,
                         clock=clk, sleep=sleep, log=lambda m: None, **kw)
    return sup, sleeps


def test_supervisor_evicts_dead_rank_and_shrinks(tmp_path):
    launch = ScriptedLaunch(["crash1", "done"])
    sup, sleeps = _supervisor(launch, tmp_path, 2, max_restarts=2)
    assert sup.run() == 0
    assert launch.widths == [2, 1]  # evicted rank 1, resumed at width 1
    assert sup.restarts == 1
    assert 1.0 in sleeps  # backoff before the relaunch


def test_supervisor_shrink_disabled_restarts_full_width(tmp_path):
    launch = ScriptedLaunch(["crash0", "done"])
    sup, _ = _supervisor(launch, tmp_path, 2, shrink=False)
    assert sup.run() == 0
    assert launch.widths == [2, 2]


def test_supervisor_respects_min_width_and_whole_gang_failures(tmp_path):
    # width 2, min 2: a dead rank cannot shrink below the floor
    launch = ScriptedLaunch(["crash0", "done"])
    sup, _ = _supervisor(launch, tmp_path, 2, min_processes=2)
    assert sup.run() == 0
    assert launch.widths == [2, 2]


def test_supervisor_budget_and_capped_backoff(tmp_path):
    launch = ScriptedLaunch(["crash0"] * 4)
    sup, sleeps = _supervisor(launch, tmp_path, 3, max_restarts=3,
                              backoff=1.0, backoff_cap=3.0)
    assert sup.run() == 1  # budget exhausted -> give up, nonzero
    assert sup.restarts == 3
    # evictions shrink 3 -> 2 -> 1; the width-1 all-dead verdict is a
    # whole-gang failure and stays at width 1 (nothing left to evict)
    assert launch.widths == [3, 2, 1, 1]
    backoffs = [s for s in sleeps if s != sup.poll_interval]
    assert backoffs == [1.0, 2.0, 3.0]  # doubling, capped at 3.0


def test_monitor_stall_verdict_names_dead_ranks(tmp_path):
    """Slow-vs-dead at the rank level: the rank whose beats STOPPED is in
    dead_ranks; the one still beating (however slowly) never is."""
    from pdnlp_tpu.parallel.watchdog import GangMonitor, Heartbeat

    clk = FakeClock()
    mon = GangMonitor([FakeProc(), FakeProc()], str(tmp_path), 2,
                      stall_timeout=30.0, clock=clk)
    hb0 = Heartbeat(str(tmp_path), 0, interval=0.0, clock=clk)
    hb1 = Heartbeat(str(tmp_path), 1, interval=0.0, clock=clk)
    clk.advance(1.0)
    hb0.beat(force=True, step=4)
    hb1.beat(force=True, step=4)
    clk.advance(31.0)
    hb0.beat(force=True, step=5, steps_per_sec=0.16)  # slow, alive
    v = mon.poll()
    assert v["kind"] == "stalled"
    assert v["dead_ranks"] == [1]


# ------------------------------------------- in-process elastic-width resume

@pytest.mark.usefixtures("ndev")
def test_elastic_width_resume_reshards_and_remaps(tmp_path, corpus_path):
    """Width 8 (ZeRO) -> snapshot mid-epoch -> resume at width 4: the
    consolidated snapshot reshards params + Adam moments onto the narrower
    mesh, the shard-deterministic sampler recomputes row assignment (twice
    the steps per epoch), and the step counter remaps by epoch fraction."""
    import jax

    from pdnlp_tpu.parallel import shard_fraction
    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import Args

    base = Args(strategy="dp", model="bert-tiny", data_path=corpus_path,
                data_limit=192, max_seq_len=32, train_batch_size=4,
                dtype="float32", dropout=0.0, attn_dropout=0.0, epochs=1,
                log_every=10 ** 9, output_dir=str(tmp_path),
                resume_every=4, pipeline="sync")
    t8, l8, _ = build_parallel_trainer(base.replace(num_devices=8),
                                       mode="zero")
    spe8 = len(l8)  # 176 train rows / (4 x 8) -> 6 steps/epoch
    assert spe8 == 6
    t8.train(l8)  # snapshots at step 4 via the async writer; drained at end
    path = base.resume_path()
    ok, reason = ckpt.verify(path)
    assert ok, reason
    assert ckpt.load_manifest(path)["meta"] == {"step": 4,
                                                "steps_per_epoch": 6}

    t4, l4, _ = build_parallel_trainer(base.replace(num_devices=4),
                                       mode="zero")
    spe4 = len(l4)  # same rows, half the width -> 11 steps/epoch
    assert spe4 == 11
    t4.load_resume(path)
    assert int(jax.device_get(t4.state["step"])) == 4  # pre-remap units
    t4.train(l4)  # remaps 4/6 -> ceil(4*11/6)=8 inside train(): steps 9..11
    assert int(jax.device_get(t4.state["step"])) == spe4
    leaf = jax.tree_util.tree_leaves(t4.state["params"])[0]
    # params AND Adam moments still ZeRO-sharded at the new width (the
    # consolidated snapshot resharded, it did not silently replicate)
    floats = {"params": t4.state["params"], "opt_state": t4.state["opt_state"]}
    assert shard_fraction(floats, leaf.sharding.mesh) < 1.5 / 4


# ------------------------------------------------- chaos (real processes)

COMMON = [
    "--model", "bert-tiny", "--data_limit", "256", "--max_seq_len", "32",
    "--train_batch_size", "4", "--dtype", "float32",
    "--dropout", "0.0", "--attn_dropout", "0.0", "--epochs", "1",
]


def _spawn(out, extra, env_extra, port, data_path=None, timeout=900):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONUNBUFFERED="1",  # SIGKILL must not eat printed loss lines
        PDNLP_SPAWN_PORT=str(port),
    )
    for k in ("COORDINATOR_ADDRESS", "PROCESS_ID", "PDNLP_FAULT_STEP",
              "PDNLP_FAULT_PROC", "PDNLP_FAULT_KIND"):
        env.pop(k, None)
    env.update(env_extra)
    data = ["--data_path", str(data_path)] if data_path else []
    # poll-with-deadline instead of subprocess.run's raise-on-timeout: a
    # loaded host that blows the (generous) deadline must yield the
    # partial stdout/stderr so the caller's skip classifier can see WHY,
    # not error the whole module's fixtures with TimeoutExpired
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--output_dir", str(out), *COMMON, *data,
         *extra],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        # kill the whole session, not just the supervisor: the spawned
        # rank subprocesses would otherwise outlive it holding the
        # coordination port — poisoning the next fixture on that port
        import signal as _signal

        try:
            os.killpg(os.getpgid(proc.pid), _signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        stdout, stderr = proc.communicate(timeout=30)
        rc = proc.returncode if proc.returncode is not None else -9
        stderr += f"\n[test] deadline ({timeout}s) exceeded — killed\n"
    return subprocess.CompletedProcess(proc.args, rc, stdout, stderr)


@pytest.fixture(scope="module")
def chaos_shrink_run(tmp_path_factory, corpus_path):
    """SIGKILL rank 1 mid-epoch; the supervisor must evict it and finish
    the run at width 1 (degrade, don't die).

    Load tolerance (the PR-10 flake): the fault trigger is STEP-count
    based, but stall detection is wall-clock — a loaded host whose XLA
    compile outruns a tight ``stall_timeout`` would read as a whole-gang
    stall and restart at full width, derailing the evict-and-shrink
    scenario.  The timeout here is deliberately generous (SIGKILL
    detection rides the exit code, not the stall clock, so a big value
    costs nothing on the pass path), and ``_spawn`` polls with a deadline
    instead of raising."""
    out = tmp_path_factory.mktemp("chaos_shrink")
    proc = _spawn(out, ["--elastic", "true", "--resume_every", "2",
                        "--stall_timeout", "300"],
                  {"PDNLP_FAULT_STEP": "5", "PDNLP_FAULT_PROC": "1",
                   "PDNLP_FAULT_KIND": "sigkill"}, port=12411,
                  data_path=corpus_path, timeout=1200)
    return proc, out


def _skip_if_multiproc_unsupported(proc):
    """A CPU backend without cross-process collectives cannot run ANY
    multi-process gang ('Multiprocess computations aren't implemented on
    the CPU backend').  Skip rather than mis-assert: the
    single-process-gang chaos variant below and the in-process
    elastic-width test carry the coverage there; this test runs fully
    where multi-process collectives exist.

    The message is checked REGARDLESS of exit code (the PR-10 skip->fail
    flake): under host load the two init-crashed ranks can be detected on
    DIFFERENT supervisor polls, so the first verdict names only one dead
    rank, the gang "shrinks" to width 1 — which this jax CAN run — and
    the run completes rc=0 as a fresh width-1 start.  That is still the
    unsupported-backend case (the 2-proc scenario under test never
    happened), and the stderr still carries the workers' message."""
    if "Multiprocess computations aren't implemented" in proc.stderr:
        pytest.skip("backend cannot run multi-process CPU gangs "
                    "(pre-existing spawn-suite incompatibility)")


@pytest.mark.slow
def test_chaos_sigkill_evicts_and_resumes_at_reduced_width(chaos_shrink_run):
    proc, out = chaos_shrink_run
    _skip_if_multiproc_unsupported(proc)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    # the supervisor classified rank 1 dead and shrank the gang
    assert "evicting dead rank(s) [1]" in proc.stderr
    assert "resuming at width 1" in proc.stderr
    assert "restart 1/" in proc.stderr
    # the restarted worker resharded + remapped onto the narrower mesh
    m = re.search(r"elastic resume: remapped step \d+ \(of (\d+)/epoch at "
                  r"save time\) -> \d+ \(of (\d+)/epoch", proc.stdout)
    assert m, proc.stdout[-3000:]
    assert int(m.group(2)) > int(m.group(1))  # fewer devices, more steps
    # no hung collectives: the run COMPLETED — every remaining optimizer
    # step ran at the new width (final train line says step total/total)
    last = re.findall(r"step：(\d+)/(\d+)", proc.stdout)[-1]
    assert last[0] == last[1], last
    assert (out / "spawn-cls.msgpack").exists()
    ok, reason = ckpt.verify(str(out / "spawn-cls.msgpack"))
    assert ok, reason


@pytest.fixture(scope="module")
def chaos_same_width_run(tmp_path_factory, corpus_path):
    """SIGKILL + restart at FULL width (--elastic_shrink false): the
    layout-matched restart must continue the golden loss trace bitwise."""
    out = tmp_path_factory.mktemp("chaos_same")
    proc = _spawn(out, ["--elastic", "true", "--elastic_shrink", "false",
                        "--resume_every", "2", "--stall_timeout", "60",
                        "--log_every", "1"],
                  {"PDNLP_FAULT_STEP": "5", "PDNLP_FAULT_PROC": "1",
                   "PDNLP_FAULT_KIND": "sigkill"}, port=12413,
                  data_path=corpus_path)
    return proc, out


@pytest.fixture(scope="module")
def undisturbed_trace_run(tmp_path_factory, corpus_path):
    """The same configuration, no chaos: the golden per-step loss trace."""
    out = tmp_path_factory.mktemp("chaos_control")
    proc = _spawn(out, ["--log_every", "1"], {}, port=12415,
                  data_path=corpus_path)
    return proc, out


def _loss_by_step(stdout):
    return {int(m.group(1)): m.group(2) for m in re.finditer(
        r"step：(\d+)/\d+ loss：([0-9.]+)", stdout)}


def test_chaos_same_width_reproduces_golden_loss_trace(
        chaos_same_width_run, undisturbed_trace_run):
    proc, _ = chaos_same_width_run
    _skip_if_multiproc_unsupported(proc)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    assert "restart 1/" in proc.stderr
    assert "evicting" not in proc.stderr  # shrink disabled: full width
    uproc, _ = undisturbed_trace_run
    assert uproc.returncode == 0, (uproc.stdout[-2000:],
                                   uproc.stderr[-3000:])
    golden = _loss_by_step(uproc.stdout)
    chaos = _loss_by_step(proc.stdout)
    assert golden, uproc.stdout[-2000:]
    # the restarted gang's lines must cover the back half of the run (the
    # crash landed at step 5 of 8) and EVERY printed step — pre-crash and
    # post-resume — must match the undisturbed run's loss to the printed
    # digit: bitwise resume over the seeded data order
    assert max(chaos) == max(golden)
    assert sum(1 for s in chaos if s > 5) >= 2
    mismatches = {s: (chaos[s], golden.get(s)) for s in chaos
                  if chaos[s] != golden.get(s)}
    assert not mismatches, mismatches


# ------------------------------------- chaos (single-process gang, any jax)

@pytest.fixture(scope="module")
def chaos_solo_run(tmp_path_factory, corpus_path):
    """A WIDTH-1 elastic gang (one preemptible worker, 4 CPU devices)
    SIGKILLed mid-epoch — runs on every image, including those whose jax
    cannot form cross-process CPU gangs."""
    out = tmp_path_factory.mktemp("chaos_solo")
    proc = _spawn(out, ["--num_processes", "1", "--elastic", "true",
                        "--resume_every", "2", "--stall_timeout", "60",
                        "--log_every", "1"],
                  {"PDNLP_FAULT_STEP": "5", "PDNLP_FAULT_PROC": "0",
                   "PDNLP_FAULT_KIND": "sigkill"}, port=12417,
                  data_path=corpus_path)
    return proc, out


@pytest.fixture(scope="module")
def solo_control_run(tmp_path_factory, corpus_path):
    out = tmp_path_factory.mktemp("chaos_solo_control")
    proc = _spawn(out, ["--num_processes", "1", "--log_every", "1"], {},
                  port=12419, data_path=corpus_path)
    return proc, out


def test_chaos_solo_sigkill_restarts_and_reproduces_trace(
        chaos_solo_run, solo_control_run):
    """SIGKILL at step 5 of 15 -> the supervisor restarts the gang from the
    async-published snapshot (step 4) and the remaining steps replay the
    golden loss trace exactly: zero lost optimizer steps, no divergence."""
    proc, out = chaos_solo_run
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    assert "restart 1/" in proc.stderr
    # a whole-gang death has no survivors to shrink to: same-width restart
    assert "evicting" not in proc.stderr
    assert re.search(r"resumed from .*resume-spawn\.msgpack at step [1-9]",
                     proc.stdout), proc.stdout[-2000:]
    uproc, _ = solo_control_run
    assert uproc.returncode == 0, (uproc.stdout[-2000:],
                                   uproc.stderr[-3000:])
    golden = _loss_by_step(uproc.stdout)
    chaos = _loss_by_step(proc.stdout)
    assert golden and max(chaos) == max(golden)
    assert sum(1 for s in chaos if s > 5) >= 2  # post-resume coverage
    mismatches = {s: (chaos[s], golden.get(s)) for s in chaos
                  if chaos[s] != golden.get(s)}
    assert not mismatches, mismatches
    last = re.findall(r"step：(\d+)/(\d+)", proc.stdout)[-1]
    assert last[0] == last[1], last  # every optimizer step ran
    ok, reason = ckpt.verify(str(out / "spawn-cls.msgpack"))
    assert ok, reason
