"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all distributed tests run on
``jax``'s host-platform backend with 8 virtual devices (the TPU-pod analog of
the reference's "only ever tested on real hardware" gap, ``SURVEY.md`` §4).

Stock JAX reads the platform, the virtual-device count and the cache switch
from the environment at import, so they are set here BEFORE ``import jax`` —
and, being environment, every child a test starts inherits them.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# NO persistent compile cache for the suite or its children.
# ``enable_compilation_cache`` points every ``parse_cli`` at one fixed
# directory, so without this switch tier-1 and its subprocess tests would
# share a CPU cache: XLA:CPU AOT entries recorded with tuning
# pseudo-features (+prefer-no-gather/-scatter) aborted the interpreter when
# RELOADED in a later process on the same host ("Fatal Python error:
# Aborted" in fetches of pipeline/MoE programs; the cpu_aot_loader warns
# about exactly this machine-feature mismatch).  Compile time is the price
# of not crashing.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy real-process cases (chaos storms, subprocess servers) "
        "excluded from tier-1 (`-m 'not slow'`)")


@pytest.fixture(scope="session")
def ndev():
    return jax.device_count()


@pytest.fixture(scope="session")
def corpus_path(tmp_path_factory):
    """A small synthetic corpus in the reference's train.json format
    (pre-tokenized, space-separated text + int label), used when the real
    corpus is absent."""
    real = "/root/reference/data/train.json"
    if os.path.exists(real):
        return real
    import json
    import random

    rng = random.Random(0)
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rows = []
    for i in range(600):
        text = " ".join(rng.choice(chars) for _ in range(rng.randint(4, 30)))
        rows.append([text, rng.randint(0, 5)])
    p = tmp_path_factory.mktemp("data") / "train.json"
    p.write_text(json.dumps(rows, ensure_ascii=False), encoding="utf-8")
    return str(p)


@pytest.fixture(scope="session")
def corpus_files(corpus_path, tmp_path_factory):
    """``Args`` fields for the corpus and ONE vocabulary file built from it:
    what a real-process gang and the in-process run it is compared with
    both have to be given (``corpus_cli``: the same as command-line arguments)."""
    return {"data_path": corpus_path,
            "vocab_path": str(tmp_path_factory.mktemp("vocab") / "vocab.txt")}


@pytest.fixture(scope="session")
def corpus_cli(corpus_files):
    """``corpus_files`` as command-line arguments."""
    return [x for k, v in corpus_files.items() for x in (f"--{k}", v)]
