"""What stands between a CPU run and a chip pass, and where the compile
cache goes.

``chip_smoke.py`` itself only passes on a TPU (``chiprun -- python
chip_smoke.py``); what can be pinned here is that it REFUSES anywhere else,
that the corpus it writes fills bert-base's vocabulary, and the host-side
decisions this bring-up changed: one placeable compile cache, a launcher
that will not start two workers on one chip's backend, a replica pool that
says when it cannot give each engine a device.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, filename):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, filename))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def chip_smoke():
    return _load("chip_smoke", "chip_smoke.py")


def test_chip_smoke_fails_in_device_phase_without_a_chip(tmp_path):
    """JAX falls back to the CPU when it finds no chip; the device phase is
    the only thing that stops such a run from passing.  It must fail before
    a model is built: no phase line, no ``ok`` line, a non-zero exit."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path / "out")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, 3), proc.stderr[-2000:]
    assert proc.stdout.strip() == "", proc.stdout
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_corpus_fills_the_published_vocabulary(chip_smoke, tmp_path):
    from pdnlp_tpu.data.corpus import load_data
    from pdnlp_tpu.data.tokenizer import build_vocab

    path = str(tmp_path / "data" / "train.json")
    texts = chip_smoke.write_corpus(path, 1152, seed=123)
    data = load_data(path)
    assert [t for t, _ in data] == texts
    assert len(build_vocab(texts)) == chip_smoke.VOCAB_ROWS == 21_128
    lengths = [len(t) for t in texts]
    assert max(lengths) == 126 and min(lengths) < 16    # seq 128 is real
    assert {y for _, y in data} == set(range(6))
    # seeded: the same file again, and another under another seed
    again = str(tmp_path / "again.json")
    assert chip_smoke.write_corpus(again, 1152, seed=123) == texts
    assert chip_smoke.write_corpus(again, 1152, seed=7) != texts


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_wins(monkeypatch, cache_config):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache; code sets nothing."""
    from pdnlp_tpu.utils.config import enable_compilation_cache, parse_cli

    jax.config.update("jax_compilation_cache_dir", "/as/jax/read/it")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compilation_cache() == "/some/dir"
    parse_cli(["--output_dir", "elsewhere"])
    assert jax.config.jax_compilation_cache_dir == "/as/jax/read/it"


def test_compile_cache_is_one_fixed_directory(monkeypatch, cache_config,
                                              tmp_path):
    """Unset, every entry point shares one absolute directory inside the
    checkout whatever the working directory and ``--output_dir`` — the path
    is part of XLA's cache key, so a cache that moves never hits."""
    from pdnlp_tpu.utils.config import COMPILATION_CACHE_DIR, parse_cli

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for cwd, out in (("a", "out1"), ("b", str(tmp_path / "out2"))):
        (tmp_path / cwd).mkdir()
        monkeypatch.chdir(tmp_path / cwd)
        parse_cli(["--output_dir", out])
        seen.append(jax.config.jax_compilation_cache_dir)
    assert seen == [COMPILATION_CACHE_DIR] * 2
    assert os.path.isabs(COMPILATION_CACHE_DIR)
    assert os.path.dirname(COMPILATION_CACHE_DIR) == REPO
    # and no second spelling of the directory anywhere in the tree
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "output", "__pycache__")]
        for f in files:
            path = os.path.join(root, f)
            if f.endswith(".py") and path != os.path.abspath(__file__) \
                    and not path.endswith("pdnlp_tpu/utils/config.py"):
                with open(path, encoding="utf-8") as fh:
                    if "jax_compilation_cache_dir" in fh.read():
                        offenders.append(os.path.relpath(path, REPO))
    assert not offenders


def test_suite_keeps_the_persistent_cache_off():
    """A fixed cache path would make tier-1 and its subprocess tests share
    XLA:CPU entries (``conftest.py``): off here, and off in every child
    through the environment."""
    assert jax.config.jax_enable_compilation_cache is False
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"


def test_spawn_launcher_refuses_two_workers_on_a_tpu_backend():
    """A chip belongs to one process: the second worker could never get
    the device.  One clear line, no hang, and no backend in the parent."""
    env = {**os.environ, "JAX_PLATFORMS": "tpu"}
    for k in ("COORDINATOR_ADDRESS", "PROCESS_ID", "NUM_PROCESSES"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "multi-tpu-spawn-cls.py"),
         "--num_processes", "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "a chip belongs to one process" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_replica_meshes_split_devices_or_say_so(capsys, ndev):
    serve_tpu = _load("serve_tpu", "serve_tpu.py")
    from pdnlp_tpu.utils.config import Args

    meshes = serve_tpu.replica_meshes(Args(), 4, use_mesh=True)
    homes = [{d.id for d in m.devices.flat} for m in meshes]
    assert all(len(h) == ndev // 4 for h in homes)
    assert len(set().union(*homes)) == ndev      # disjoint slices
    assert capsys.readouterr().err == ""

    assert serve_tpu.replica_meshes(Args(), 2 * ndev, use_mesh=True) \
        == [None] * (2 * ndev)
    err = capsys.readouterr().err
    assert "default device" in err and f"{ndev} device(s)" in err


def test_missing_corpus_says_what_data_path_must_be():
    from pdnlp_tpu.data.corpus import load_data

    with pytest.raises(FileNotFoundError, match="--data_path must name"):
        load_data("/nonexistent/train.json")



def test_auto_is_pinned_to_xla_where_gspmd_partitions_the_program(capsys):
    """Mosaic kernels cannot be partitioned automatically (the described
    4-device compile of the dp step refused the fused-CE kernel): under a
    multi-device jit mesh ``auto`` means XLA, said once; an explicit
    ``pallas`` passes through to fail at lowering as the error it is."""
    from pdnlp_tpu.ops import attention
    from pdnlp_tpu.parallel import make_mesh
    from pdnlp_tpu.serve.batcher import resolve_serve_pack

    one, four = make_mesh(num_devices=1), make_mesh(num_devices=4)
    attention._FALLBACK_WARNED.discard(("mesh", "fused_ce"))
    assert attention.pin_auto_for_mesh("auto", None) == "auto"
    assert attention.pin_auto_for_mesh("auto", one) == "auto"
    assert capsys.readouterr().err == ""
    for _ in range(2):
        assert attention.pin_auto_for_mesh("auto", four, "fused_ce") == "xla"
    assert capsys.readouterr().err.count("4-device mesh") == 1
    assert attention.pin_auto_for_mesh("pallas", four) == "pallas"
    # the serve packing decision follows the engine's pinned request
    assert resolve_serve_pack("auto", 128, "xla") is False
    assert resolve_serve_pack("on", 128, "xla") is True
