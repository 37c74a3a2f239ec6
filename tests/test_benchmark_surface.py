"""The names ``benchmark/`` holds the serving program to (its README's "touch
points", plus five the kinds read beyond that list), one case a name.

``benchmark/tests/`` is not in tier-1 and no PR but a ``benchmark`` one may
edit the kinds, so this is what fails first when a refactor of
``serve/decode.py`` renames, drops or re-types something they read: each
case exercises its name the way ``benchmark/kinds/serve.py`` /
``closed_loop_latent_moe.py`` do, on one BERT and one latent engine that
served a stream each.  Also here, because they are the same kind of promise:
``Args.kv_layout`` keeps its one value (the kinds pass it) and refuses the
layout that is gone, and ``chip_smoke.py`` finds the chip's peak where it
lives now."""
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.obs.trace import Tracer
from pdnlp_tpu.serve.decode import DecodeBatcher, PagedDecodeEngine
from pdnlp_tpu.utils.config import Args, parse_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
PROGRAMS = {"_pdecode_fn": "_jit_pdecode", "_pchunk_fn": "_jit_pchunk",
            "_prefill_fn": "_jit_prefill", "_pinsert_fn": "_jit_pinsert",
            "_pcow_fn": "_jit_pcow"}


def record_lowerings(eng, into):
    """Stand between the engine and its jitted programs: the FIRST call of
    each lowers the same arguments once more and keeps the module's name."""
    for fn_name, attr in PROGRAMS.items():
        jitted = getattr(eng, attr)

        def call(*a, _jitted=jitted, _name=fn_name):
            if _name not in into:
                into[_name] = _jitted.lower(*a).as_text().split("\n", 1)[0]
            return _jitted(*a)

        call.__wrapped__ = jitted
        setattr(eng, attr, call)


def served(model, **kw):
    """An engine built the kinds' way, warmed, that served one stream."""
    tok = WordPieceTokenizer(build_vocab(TEXTS, size=128))
    args = Args(model=model, kv_layout="paged", **kw)
    # a tracer of its own, enabled (the fetch leaf records ``expert_load``):
    # ``Args.trace`` would turn on the process-global one, whose ring other
    # test files of the same worker read
    eng = PagedDecodeEngine(args, tokenizer=tok, buckets=(16, 32),
                            prefill_rows=2, tracer=Tracer(enabled=True))
    # the kinds replace the weights the engine made with their own
    tree = (eng.params, eng.head)
    eng.params = eng.head = None
    eng.params, eng.head = tree
    lowered = {}
    record_lowerings(eng, lowered)
    batcher = DecodeBatcher(eng, max_waiting=8, default_max_new=4)
    batcher.eos_id = -1
    started = batcher.start()
    batcher.warmup()
    jax.block_until_ready((eng._cache_k, eng._cache_v, eng._pools))
    traced = eng.metrics.retraces.value
    stream = batcher.submit_ids(list(range(5, 14)), max_new_tokens=5)
    tokens = stream.result(timeout=300)
    seen = types.SimpleNamespace(
        eng=eng, batcher=batcher, started=started, stream=stream,
        tokens=tokens, lowered=lowered, traced=traced, slot=stream.slot)
    batcher.stop(drain=False)
    return seen


@pytest.fixture(scope="module")
def bert():
    return served("bert-tiny", decode_slots=2, decode_max_len=32,
                  max_seq_len=32)


@pytest.fixture(scope="module")
def latent():
    return served("ax-k1-share-tiny", decode_slots=2, decode_max_len=32,
                  max_seq_len=32)


def counter(name):
    return lambda s: getattr(s.batcher.metrics, name).value


#: name -> what the benchmark does with it, as a predicate over a served run
SURFACE = {
    # --- PagedDecodeEngine
    "engine.params": lambda s: len(jax.tree_util.tree_leaves(s.eng.params)) > 0,
    "engine.head": lambda s: len(jax.tree_util.tree_leaves(s.eng.head)) > 0,
    "engine.slots": lambda s: s.eng.slots == 2,
    "engine.max_len": lambda s: s.eng.max_len == 32,
    "engine.n_pages": lambda s: s.eng.n_pages == 2 * 2,     # 2 slots x 32/16
    "engine.allocator.used_pages":
        lambda s: 0 <= s.eng.allocator.used_pages <= s.eng.n_pages,
    "engine.prefix.snapshot()":
        lambda s: {"hits_full", "hits_partial", "misses"}
        <= set(s.eng.prefix.snapshot()),
    "engine.metrics.retraces":
        lambda s: s.eng.metrics.retraces.value == s.traced,  # none served
    "engine.decode_batch":
        lambda s: len(s.eng.decode_batch(
            np.zeros(2, np.int32), np.zeros(2, np.int32), live=0).ids) == 2,
    # --- DecodeBatcher
    "batcher.start()": lambda s: s.started is s.batcher,
    "batcher.warmup()": lambda s: s.traced > 0,
    "batcher.submit_ids()": lambda s: len(s.tokens) == 5,
    "batcher.stop(drain=False)": lambda s: s.batcher._worker is None,
    "metrics.decode_steps_total": lambda s: counter("decode_steps_total")(s) >= 4,
    "metrics.prefills_total": lambda s: counter("prefills_total")(s) == 1,
    "metrics.prefill_tokens_total":
        lambda s: counter("prefill_tokens_total")(s) == 9,
    "metrics.tokens_out_total": lambda s: counter("tokens_out_total")(s) == 5,
    "metrics.rejected_total": lambda s: counter("rejected_total")(s) == 0,
    "metrics.deadline_expired_total":
        lambda s: counter("deadline_expired_total")(s) == 0,
    "rmetrics.slot_occupancy":
        lambda s: s.batcher.rmetrics.slot_occupancy.snapshot()["count"] >= 1,
    # --- a stream
    "stream.emitted": lambda s: list(s.stream.emitted) == s.tokens,
    "stream.done()": lambda s: s.stream.done() is True,
    "stream.result()": lambda s: s.stream.result(timeout=0.0) == s.tokens,
    "stream.slot": lambda s: s.slot in (0, 1),
    # --- read by the kinds beyond the README's list
    "engine._cache_k":
        lambda s: s.eng._cache_k is s.eng._pools[0]
        and s.eng._cache_k.shape[1:3] == (s.eng.n_pages, s.eng.page_sz),
    "engine._cache_v":
        lambda s: s.eng._cache_v is (s.eng._pools[1]
                                     if len(s.eng._pools) > 1 else None),
    "engine._pools":
        lambda s: isinstance(s.eng._pools, tuple) and len(s.eng._pools)
        == len(s.eng.family.pool_widths(s.eng.cfg)),
    'engine.kv_snapshot()["layout"]':
        lambda s: s.eng.kv_snapshot()["layout"] == "paged"
        and {"kv_pool_bytes", "weights_bytes", "pages", "prefix"}
        <= set(s.eng.kv_snapshot()),
}
for _fn in PROGRAMS:
    SURFACE[f"program {_fn}"] = (
        lambda s, _fn=_fn: s.lowered[_fn].startswith(f"module @jit_{_fn} ")
        and getattr(s.eng, PROGRAMS[_fn]).__wrapped__.__name__ == _fn)


@pytest.mark.parametrize("name", list(SURFACE))
def test_a_name_the_benchmark_reads_keeps_working(name, bert, latent):
    for run in (bert, latent):
        assert SURFACE[name](run), (name, run.eng.args.model)


def test_expert_load_counts_the_held_experts_of_the_latent_family(bert,
                                                                  latent):
    """``engine.expert_load`` (``closed_loop_latent_moe.py``): assignments to
    each held expert, summed over the launches whose fetch leaf recorded;
    ``None`` for a family without experts."""
    assert bert.eng.expert_load is None
    load = latent.eng.expert_load
    assert load is not None and load.dtype == np.int64
    assert load.shape == (latent.eng.cfg.experts_held,)
    assert int(load.sum()) > 0


# ---------------------------------------------------- the layout that is gone

def test_the_slot_layout_is_refused_in_one_sentence(capsys):
    assert Args(kv_layout="paged").kv_layout == "paged"
    with pytest.raises(ValueError, match="slot KV layout is gone") as e:
        Args(kv_layout="slots")
    assert "\n" not in str(e.value)
    with pytest.raises(ValueError, match="only value"):
        Args().replace(kv_layout="anything-else")
    with pytest.raises(SystemExit) as ex:      # the CLI: usage + the sentence
        parse_cli(["--kv_layout", "slots"])
    assert ex.value.code == 2
    assert "slot KV layout is gone" in capsys.readouterr().err


def test_serve_tpu_refuses_the_slot_layout_before_it_builds_anything():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "serve_tpu.py"), "--decode",
         "--model", "bert-tiny", "--no_mesh", "--kv_layout", "slots"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), input="")
    assert proc.returncode == 2
    assert "slot KV layout is gone" in proc.stderr
    assert "Traceback" not in proc.stderr


# ------------------------------------------------------- the chip's peak

@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v4", 275e12),
    ("TPU v6 lite", 918e12), ("cpu", None)])
def test_chip_smoke_finds_the_chips_peak_in_its_new_home(kind, peak):
    from pdnlp_tpu.utils import profiling

    assert profiling.bf16_peak(types.SimpleNamespace(device_kind=kind)) == peak
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert "from pdnlp_tpu.utils.profiling import bf16_peak" in src
    assert "import bench" not in src
