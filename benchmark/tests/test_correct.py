"""``correct`` has to be able to come out false.

1. The harness is driven past its look for a chip (``--rehearse``: the CPU,
   the configuration's tiny size) with the timed path broken underneath, and
   ``correct`` comes out false; unbroken, it comes out true.
2. The control — the reference, computed one precision below what the
   configuration states (fp8 for bfloat16), put in the program's place —
   comes out as not correct under the configuration's own limits, on three
   seeds, at a size a test can hold.  (The chip readings at the cells' own
   sizes that the limits were set from are in ``PERF.md``.)
"""
import argparse
import os

import pytest

from benchmark import common
from benchmark.run import run_cell


def other_masks(monkeypatch):
    """The reference under another seed's dropout masks: what a program
    that drew its masks otherwise would be compared with."""
    from benchmark.reference import model

    stream = model.dropout_masks
    monkeypatch.setattr(model, "dropout_masks",
                        lambda seed, step, **kw: stream(seed + 1, step, **kw))


def rehearse(workload, seed=5, seconds=2.0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=0, rehearse=True)
    assert run_cell(ns) == common.REHEARSAL_EXIT
    return ns.result


def frozen_step(monkeypatch):
    """The program's trainer, with steps that return their state unchanged."""
    import jax.numpy as jnp

    from pdnlp_tpu.train import run as program

    build = program.build_parallel_trainer

    def broken(*a, **kw):
        trainer, train_loader, dev_loader = build(*a, **kw)
        k = trainer.args.fuse_steps
        trainer.multi_step = lambda s, b: (s, {"loss": jnp.zeros((k,))})
        trainer.train_step = lambda s, b: (s, {"loss": jnp.zeros(())})
        return trainer, train_loader, dev_loader

    monkeypatch.setattr(program, "build_parallel_trainer", broken)


def wrong_token(monkeypatch):
    """The program's engine, every decode step's logits shifted by one id."""
    import numpy as np

    from pdnlp_tpu.serve.decode import PagedDecodeEngine

    inner = PagedDecodeEngine.decode_batch
    monkeypatch.setattr(
        PagedDecodeEngine, "decode_batch",
        lambda self, *a, **k: np.roll(inner(self, *a, **k), 1, axis=-1))


@pytest.mark.parametrize("workload,break_it,failing", [
    ("finetune-pad128", frozen_step, {"loss_abs", "moment_rel", "delta_rel"}),
    ("finetune-pad128", other_masks, {"loss_abs", "moment_rel"}),
    ("decode-file-saturated-mixedout", wrong_token, {"served_logit_gap"}),
])
def test_a_broken_timed_path_is_not_correct(workload, break_it, failing,
                                            monkeypatch):
    monkeypatch.setenv("BENCHMARK_KEEP_SAMPLES", "1")
    sound = rehearse(workload)
    assert sound["correct"], sound["checks"]
    kept = os.path.join(common.work_dir(workload), "samples_5.json")
    if "served_logit_gap" in failing:    # a decode run keeps its sample
        rows = common.load_json(kept)["requests"]
        assert rows and all(len(r) == 4 for r in rows)
        os.remove(kept)
        # the closed loop's window by thirds: all of its tokens, in order
        thirds, c = sound["obs"]["thirds"], sound["obs"]["counters"]
        assert len(thirds) == 3 and all(t["tokens_per_s"] > 0 for t in thirds)
        mean = sum(t["tokens_per_s"] for t in thirds) / 3
        assert mean == pytest.approx(c["tokens_seen"] / c["window_s"], rel=0.2)
    break_it(monkeypatch)
    broken = rehearse(workload)
    assert not broken["correct"]
    bad = {r["check"] for r in broken["checks"] if not r["ok"]}
    assert bad & failing, broken["checks"]


def test_a_refused_request_waits_the_whole_drain(monkeypatch):
    """A request the program refuses is in ``failed`` AND in the tail of the
    time to first token: refusing load cannot improve ``ttft_p95_ms``."""
    from pdnlp_tpu.serve.decode import DecodeBatcher

    from benchmark.kinds import serve

    inner, calls = DecodeBatcher.submit_ids, [0]

    def every_third_refused(self, *a, **k):
        calls[0] += 1
        if calls[0] % 3 == 0:
            raise RuntimeError("queue full")
        return inner(self, *a, **k)

    monkeypatch.setattr(DecodeBatcher, "submit_ids", every_third_refused)
    res = rehearse("decode-chat-belowknee", seconds=3.0)
    assert res["failed"] >= res["attempted"] // 3 > 0
    assert res["end_to_end"]["ttft_p95_ms"] == serve.DRAIN_S * 1e3


@pytest.mark.parametrize("strategy", ["dp", "zero"])
def test_the_configuration_names_the_strategy(strategy, monkeypatch):
    """``program.strategy`` reaches ``build_parallel_trainer`` as its mode: a
    four-chip cell of another strategy is a configuration file, not code.
    Driven on four virtual devices at the tiny size, dropout on, and held to
    the reference like any run."""
    from pdnlp_tpu.train import run as program

    from benchmark.kinds import train_epochs
    from benchmark.run import Context

    build, seen = program.build_parallel_trainer, {}

    def spy(args, **kw):
        seen.update(kw)
        return build(args, **kw)

    monkeypatch.setattr(program, "build_parallel_trainer", spy)
    cell = common.Cell("finetune-pad128")
    cell.chips = 4
    cell.config["program"]["strategy"] = strategy
    ns = argparse.Namespace(seed=11, seconds=1.0, trace=0, rehearse=True)
    devices, peaks = common.find_devices(cell.chips, True)
    res = train_epochs.run(cell, Context(ns, cell, devices, peaks))
    assert seen == {"mode": strategy, "explicit_collectives": False}
    assert res["correct"], res["checks"]


SMALL = {"vocab_size": 600, "hidden_size": 768, "num_hidden_layers": 4,
         "num_attention_heads": 12, "intermediate_size": 3072,
         "max_position_embeddings": 64, "type_vocab_size": 2, "num_labels": 6,
         "layer_norm_eps": 1e-12}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_in_fp8_is_not_correct(seed):
    import numpy as np

    from benchmark.kinds import train_epochs
    from benchmark.reference import model, weights

    cfg = common.load_json(common.HERE, "configs", "bert-base-wwm-ext-cls.json")
    recipe = {**cfg["recipe"], "total_steps": 144,
              "dropout": {"seed": seed, "impl": "rbg", "rates": (
                  cfg["hidden_dropout_prob"],
                  cfg["attention_probs_dropout_prob"])}}
    rng = np.random.default_rng(seed)
    rows, seq = 8, 48
    lens = rng.integers(8, seq, rows)
    batch = {"input_ids": rng.integers(5, 600, (rows, seq)).astype(np.int32),
             "token_type_ids": np.zeros((rows, seq), np.int32),
             "attention_mask": (np.arange(seq)[None] < lens[:, None]).astype(np.int32),
             "label": rng.integers(0, 6, rows).astype(np.int32),
             "example_weight": np.ones(rows, np.float32)}
    w = weights.make_weights(seed, SMALL)
    kw = dict(heads=12, eps=1e-12)

    def as_first(prec):
        losses, mu, p = model.train_steps(w, [batch] * 2, recipe, prec=prec,
                                          dropout=recipe["dropout"], **kw)
        return {"batch": {k: np.stack([v, v]) for k, v in batch.items()},
                "n": 2, "losses": losses, "mu": model.leaf_norms(mu),
                "delta": model.leaf_norms({k: p[k] - w[k] for k in p})}

    sound = train_epochs.compare(as_first("bf16"), seed, SMALL, recipe, cfg["check"])
    control = train_epochs.compare(as_first("fp8"), seed, SMALL, recipe, cfg["check"])
    assert sound.correct, sound.rows
    assert not control.correct, control.rows


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_in_fp8_is_not_correct(seed):
    import random

    from benchmark.kinds import serve

    cfg = common.load_json(common.HERE, "configs", "bert-base-wwm-ext-causal.json")
    sizes = {**SMALL, "vocab_size": 3000}
    rng = random.Random(seed)
    served = [([rng.randrange(5, 3000) for _ in range(24)],
               [rng.randrange(5, 3000) for _ in range(30)]) for _ in range(3)]
    limit = cfg["check"]["served_logit_gap"]
    bf16 = serve.reference_gaps(served, seed, sizes, (3,), lowprec="bf16")
    fp8 = serve.reference_gaps(served, seed, sizes, (3,), lowprec="fp8")
    assert max(max(g) for g in bf16) <= limit
    assert max(max(g) for g in fp8) > limit
