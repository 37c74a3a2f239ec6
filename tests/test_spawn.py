"""Multi-process launcher EXECUTION tests — two real OS processes.

The reference actually forks workers and rendezvouses over TCP
(``/root/reference/multi-gpu-distributed-mp-cls.py:265-266,361``); these
tests hold the spawn launcher to the same standard: fork 2 processes on the
CPU backend (4 virtual devices each -> one 8-device global mesh over gloo),
train for real, and require loss/parameter parity with a single-process run
of the identical global configuration.  This also executes the genuinely
multi-process branches that are dead code under one process:
``jax.distributed.initialize``, cross-host ``make_array_from_process_local_
data``, and ``checkpoint.consolidate``'s ``process_allgather``.
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
def spawn_run(tmp_path_factory, corpus_cli):
    """Run the spawn launcher once (2 procs x 4 virtual CPU devices)."""
    out = tmp_path_factory.mktemp("spawn")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--output_dir", str(out), *COMMON_ARGS,
         *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc, out


def test_spawn_completes_and_checkpoints(spawn_run):
    proc, out = spawn_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    # the consolidated (process_allgather) checkpoint was written by rank 0
    assert (out / "spawn-cls.msgpack").exists()
    # both workers rendezvoused into ONE 8-device 2-process runtime
    assert "process 0/2" in proc.stdout
    assert "mesh: {'data': 8}" in proc.stdout


def test_spawn_matches_single_process(spawn_run, ndev, corpus_files):
    """Same global batch (4 x 4 x 2 == 4 x 8), same seed, no dropout ->
    the 2-process run must reproduce the single-process loss trace and
    final parameters (up to collective reassociation)."""
    proc, out = spawn_run
    assert proc.returncode == 0, proc.stderr[-3000:]

    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="spawn", model="bert-tiny", data_limit=600,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1,
                output_dir=str(out), log_every=1, **corpus_files)
    trainer, train_loader, dev_loader = build_parallel_trainer(args, mode="dp")
    single_losses = []
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))
        single_losses.append(float(m["loss"]))

    # --- loss-trace parity (the reference's golden-loss ritual) ---
    spawn_losses = [float(x) for x in
                    re.findall(r"loss：([0-9.]+)", proc.stdout)]
    n = min(len(spawn_losses), len(single_losses))
    assert n >= 5, f"too few logged losses: {proc.stdout[-2000:]}"
    np.testing.assert_allclose(spawn_losses[:n], single_losses[:n],
                               rtol=2e-4, atol=2e-5)

    # --- final-parameter parity via the consolidated checkpoint ---
    import jax

    restored = ckpt.load_params(str(out / "spawn-cls.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)


@pytest.fixture(scope="module")
def spawn_zero_run(tmp_path_factory, corpus_cli):
    """``--mode zero`` across 2 real processes x 2 CPU devices: a 4-way
    ``{"data": 4}`` mesh whose param/moment shards live on BOTH processes —
    the reference's actual DeepSpeed deployment shape
    (``/root/reference/multi-gpu-deepspeed-cls.py:299-302``: ZeRO-3
    partitioning *across processes*)."""
    out = tmp_path_factory.mktemp("spawn_zero")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PDNLP_SPAWN_PORT="12381",  # own rendezvous port per gang fixture
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--mode", "zero",
         "--ckpt_name", "zero-spawn.msgpack",
         "--output_dir", str(out), *COMMON_ARGS, *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc, out


def test_spawn_zero_executes_across_processes(spawn_zero_run):
    proc, out = spawn_zero_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "mode: zero" in proc.stdout
    assert "mesh: {'data': 4}" in proc.stdout
    assert "process 0/2" in proc.stdout
    # the consolidated checkpoint exists: cross-process shards were
    # all-gathered (checkpoint.consolidate -> process_allgather) and rank 0
    # wrote one full single-file artifact
    assert (out / "zero-spawn.msgpack").exists()


def test_spawn_zero_matches_single_process(spawn_zero_run, ndev, corpus_files):
    """The 2-process ZeRO run must reproduce a single-process run of the
    same global configuration (4-way sharded state, global batch 16), and
    its consolidated checkpoint must reassemble the full parameters."""
    proc, out = spawn_zero_run
    assert proc.returncode == 0, proc.stderr[-3000:]

    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="zero-spawn-ref", model="bert-tiny", data_limit=600,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1, num_devices=4,
                output_dir=str(out), log_every=1, **corpus_files)
    trainer, train_loader, _ = build_parallel_trainer(args, mode="zero")
    single_losses = []
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))
        single_losses.append(float(m["loss"]))

    spawn_losses = [float(x) for x in
                    re.findall(r"loss：([0-9.]+)", proc.stdout)]
    n = min(len(spawn_losses), len(single_losses))
    assert n >= 5, f"too few logged losses: {proc.stdout[-2000:]}"
    np.testing.assert_allclose(spawn_losses[:n], single_losses[:n],
                               rtol=2e-4, atol=2e-5)

    import jax

    restored = ckpt.load_params(str(out / "zero-spawn.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)


@pytest.fixture(scope="module")
def spawn_pp_run(tmp_path_factory, corpus_cli):
    """``--mode pp`` across 2 real processes x 1 CPU device each: a
    ``{"stage": 2}`` pipeline whose stage boundary IS the process boundary —
    every ``ppermute`` activation transfer crosses processes."""
    out = tmp_path_factory.mktemp("spawn_pp")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PDNLP_SPAWN_PORT="12382",  # own rendezvous port per gang fixture
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--mode", "pp",
         "--mesh_shape", '{"stage": 2}', "--microbatches", "2",
         "--ckpt_name", "pp-spawn.msgpack",
         "--output_dir", str(out), *COMMON_ARGS, *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc, out


def test_spawn_pp_executes_across_processes(spawn_pp_run):
    proc, out = spawn_pp_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "stages: 2 x 1 layers" in proc.stdout
    assert "process 0/2" in proc.stdout
    assert (out / "pp-spawn.msgpack").exists()


def test_spawn_pp_matches_single_process(spawn_pp_run, ndev, corpus_files):
    """The cross-process pipeline must reproduce an in-process run of the
    identical {"stage": 2} mesh (same global batch, same microbatching)."""
    proc, out = spawn_pp_run
    assert proc.returncode == 0, proc.stderr[-3000:]

    from pdnlp_tpu.train.run import build_pipeline_trainer
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="pp-spawn-ref", model="bert-tiny", data_limit=600,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1,
                mesh_shape={"stage": 2}, microbatches=2,
                output_dir=str(out), log_every=1, **corpus_files)
    trainer, train_loader, _ = build_pipeline_trainer(args)
    single_losses = []
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))
        single_losses.append(float(m["loss"]))

    spawn_losses = [float(x) for x in
                    re.findall(r"loss：([0-9.]+)", proc.stdout)]
    n = min(len(spawn_losses), len(single_losses))
    assert n >= 5, f"too few logged losses: {proc.stdout[-2000:]}"
    np.testing.assert_allclose(spawn_losses[:n], single_losses[:n],
                               rtol=2e-4, atol=2e-5)

    import jax

    restored = ckpt.load_params(str(out / "pp-spawn.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)


def test_spawn_tp_across_processes(tmp_path, corpus_files, corpus_cli):
    """``--mode tp`` with the MODEL axis spanning the process boundary
    (``{"data": 1, "model": 2}`` over 2 procs x 1 device): the data axis is
    process-replicated — every host feeds the full batch
    (``local_data_extent``) — and each attention/MLP block's features live
    half per process.  Pins the launcher's "any sharding across processes"
    claim for tp; zero/pp have their own fixtures above."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PDNLP_SPAWN_PORT="12383",
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--mode", "tp",
         "--mesh_shape", '{"data": 1, "model": 2}',
         "--ckpt_name", "tp-spawn.msgpack",
         "--output_dir", str(tmp_path), *COMMON_ARGS, *corpus_cli,
         "--data_limit", "300"],  # after COMMON_ARGS: the override wins
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "mode: tp" in proc.stdout
    assert "process 0/2" in proc.stdout
    assert (tmp_path / "tp-spawn.msgpack").exists()

    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="tp-spawn-ref", model="bert-tiny", data_limit=300,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1, num_devices=2,
                mesh_shape={"data": 1, "model": 2},
                output_dir=str(tmp_path), log_every=1, **corpus_files)
    trainer, train_loader, _ = build_parallel_trainer(args, mode="tp")
    single_losses = []
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))
        single_losses.append(float(m["loss"]))

    spawn_losses = [float(x) for x in
                    re.findall(r"loss：([0-9.]+)", proc.stdout)]
    n = min(len(spawn_losses), len(single_losses))
    assert n >= 5, f"too few logged losses: {proc.stdout[-2000:]}"
    np.testing.assert_allclose(spawn_losses[:n], single_losses[:n],
                               rtol=2e-4, atol=2e-5)

    import jax

    restored = ckpt.load_params(str(tmp_path / "tp-spawn.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)


@pytest.fixture(scope="module")
def spawn_sp_run(tmp_path_factory, corpus_cli):
    """``--mode sp`` across 2 real processes x 1 CPU device each: a
    ``{"data": 1, "seq": 2}`` mesh whose sequence axis IS the process
    boundary — ring attention's ``ppermute`` KV rotation crosses processes
    every layer."""
    out = tmp_path_factory.mktemp("spawn_sp")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PDNLP_SPAWN_PORT="12383",  # own rendezvous port per gang fixture
    )
    env.pop("COORDINATOR_ADDRESS", None)
    env.pop("PROCESS_ID", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2", "--mode", "sp",
         "--mesh_shape", '{"data": 1, "seq": 2}',
         "--ckpt_name", "sp-spawn.msgpack",
         "--output_dir", str(out), *COMMON_ARGS, *corpus_cli],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc, out


def test_spawn_sp_executes_across_processes(spawn_sp_run):
    proc, out = spawn_sp_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ring axis: seq (local seq 16)" in proc.stdout
    assert "process 0/2" in proc.stdout
    assert (out / "sp-spawn.msgpack").exists()


def test_spawn_sp_matches_single_process(spawn_sp_run, ndev, corpus_files):
    """The cross-process ring must reproduce an in-process run of the
    identical {"data": 1, "seq": 2} mesh — same global batch, same seeded
    streams; the only difference is WHERE the ring's ppermute hops land."""
    proc, out = spawn_sp_run
    assert proc.returncode == 0, proc.stderr[-3000:]

    from pdnlp_tpu.train.run import build_sp_trainer
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.utils.config import Args

    args = Args(strategy="sp-spawn-ref", model="bert-tiny", data_limit=600,
                max_seq_len=32, train_batch_size=4, dtype="float32",
                dropout=0.0, attn_dropout=0.0, epochs=1,
                mesh_shape={"data": 1, "seq": 2}, num_devices=2,
                output_dir=str(out), log_every=1, **corpus_files)
    trainer, train_loader, _ = build_sp_trainer(args)
    single_losses = []
    for batch in train_loader:
        trainer.state, m = trainer.train_step(trainer.state, trainer.put(batch))
        single_losses.append(float(m["loss"]))

    spawn_losses = [float(x) for x in
                    re.findall(r"loss：([0-9.]+)", proc.stdout)]
    n = min(len(spawn_losses), len(single_losses))
    assert n >= 5, f"too few logged losses: {proc.stdout[-2000:]}"
    np.testing.assert_allclose(spawn_losses[:n], single_losses[:n],
                               rtol=2e-4, atol=2e-5)

    import jax

    restored = ckpt.load_params(str(out / "sp-spawn.msgpack"),
                                trainer.state["params"])
    flat_a = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(restored)])
    flat_b = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(trainer.state["params"])])
    np.testing.assert_allclose(flat_a, flat_b, rtol=1e-3, atol=1e-5)
