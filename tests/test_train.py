"""Train-layer tests: optimizer decay mask, train step learns, checkpoint
roundtrip, Trainer end-to-end on a tiny synthetic task."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.train import (
    Trainer, build_optimizer, checkpoint, decay_mask, init_state,
    make_eval_step, make_train_step,
)
from pdnlp_tpu.utils.config import Args


@pytest.fixture()
def args(tmp_path):
    return Args(model="bert-tiny", output_dir=str(tmp_path), log_every=10,
                train_batch_size=8, dev_batch_size=8)


@pytest.fixture()
def cfg():
    return get_config("bert-tiny", vocab_size=64, num_labels=6)


def _state_and_tx(cfg, args):
    params = bert.init_params(jax.random.key(0), cfg)
    tx = build_optimizer(params, args)
    return init_state(jax.random.key(0), cfg, tx, rng=jax.random.key(1)), tx


def _batch(cfg, n=8, s=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, cfg.vocab_size, (n, s)).astype(np.int32)
    # learnable rule: label = first token id mod 6
    labels = (ids[:, 1] % 6).astype(np.int32)
    return {
        "input_ids": jnp.asarray(ids),
        "token_type_ids": jnp.zeros((n, s), jnp.int32),
        "attention_mask": jnp.ones((n, s), jnp.int32),
        "label": jnp.asarray(labels),
        "example_weight": jnp.ones((n,), jnp.float32),
    }


def test_decay_mask_groups(cfg, args):
    params = bert.init_params(jax.random.key(0), cfg)
    mask = decay_mask(params)
    assert mask["pooler"]["kernel"] is True
    assert mask["pooler"]["bias"] is False
    assert mask["layers"]["attn_ln"]["scale"] is False
    assert mask["layers"]["attn_ln"]["bias"] is False
    assert mask["layers"]["q"]["kernel"] is True
    assert mask["embeddings"]["ln"]["scale"] is False
    assert mask["embeddings"]["word"] is True


def test_train_step_reduces_loss(cfg, args):
    state, tx = _state_and_tx(cfg, args)
    fast = args.replace(learning_rate=1e-3)
    step = make_train_step(cfg, build_optimizer(state["params"], fast), fast)
    batch = _batch(cfg)
    first = None
    for _ in range(30):
        state, m = step(state, batch)
        if first is None:
            first = float(m["loss"])
    assert int(state["step"]) == 30
    assert float(m["loss"]) < first * 0.7, (first, float(m["loss"]))


def test_filler_rows_do_not_affect_grads(cfg, args):
    """A batch padded with weight-0 filler must produce identical updates."""
    state, tx = _state_and_tx(cfg, args)
    step = make_train_step(cfg, tx, args)
    b8 = _batch(cfg, n=8)
    padded = {k: jnp.concatenate([v, v], 0) for k, v in b8.items()}
    padded["example_weight"] = jnp.concatenate(
        [b8["example_weight"], jnp.zeros((8,), jnp.float32)], 0)
    s1, m1 = step(jax.tree_util.tree_map(jnp.copy, state), b8)
    s2, m2 = step(jax.tree_util.tree_map(jnp.copy, state), padded)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    a = jax.tree_util.tree_leaves(s1["params"])
    b = jax.tree_util.tree_leaves(s2["params"])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-4, atol=1e-6)


def test_eval_step_sums(cfg, args):
    state, tx = _state_and_tx(cfg, args)
    ev = make_eval_step(cfg, args)
    batch = _batch(cfg)
    m = ev(state["params"], batch)
    assert float(m["weight"]) == 8.0
    assert 0 <= float(m["correct"]) <= 8
    assert m["pred"].shape == (8,)


def test_checkpoint_roundtrip(cfg, args, tmp_path):
    state, tx = _state_and_tx(cfg, args)
    step = make_train_step(cfg, tx, args)
    state, _ = step(state, _batch(cfg))
    p = str(tmp_path / "full.msgpack")
    checkpoint.save_state(p, state)
    blank, _ = _state_and_tx(cfg, args)
    restored = checkpoint.load_state(p, blank)
    assert int(restored["step"]) == 1
    for x, y in zip(jax.tree_util.tree_leaves(state["params"]),
                    jax.tree_util.tree_leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # params-only checkpoint (the state_dict analog)
    p2 = str(tmp_path / "params.msgpack")
    checkpoint.save_params(p2, state)
    rp = checkpoint.load_params(p2, blank["params"])
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(rp)[0]),
        np.asarray(jax.tree_util.tree_leaves(state["params"])[0]))


def test_latest_orders_step_family_by_step_not_mtime(tmp_path):
    """One step family (same stem, trailing -<n>): the step number orders
    the candidates even when a cp -p restore or a coarse-mtime filesystem
    scrambles/ties the timestamps."""
    for step, mtime in (("100", 3000), ("1500", 1000), ("200", 2000)):
        p = tmp_path / f"ckpt-{step}.msgpack"
        p.write_bytes(b"x")
        os.utime(p, (mtime, mtime))  # newest mtime is NOT the newest step
    got = checkpoint.latest(str(tmp_path))
    assert os.path.basename(got) == "ckpt-1500.msgpack"


def test_latest_mixed_names_fall_back_to_mtime(tmp_path):
    """Interior/attached digits are not steps: pretrained-e5 (epoch tag)
    must never outrank a newer zero2-cls on its digit."""
    old = tmp_path / "pretrained-e5.msgpack"
    new = tmp_path / "zero2-cls.msgpack"
    old.write_bytes(b"x")
    new.write_bytes(b"x")
    os.utime(old, (1000, 1000))
    os.utime(new, (2000, 2000))
    got = checkpoint.latest(str(tmp_path))
    assert os.path.basename(got) == "zero2-cls.msgpack"
    # deterministic tie-break on equal mtimes (coarse-mtime tie)
    os.utime(old, (2000, 2000))
    assert checkpoint.latest(str(tmp_path)) is not None


class _ListLoader:
    """Minimal loader: fixed list of batches, sampler-compatible."""

    def __init__(self, batches):
        self.batches = batches

    def __len__(self):
        return len(self.batches)

    def set_epoch(self, e):
        pass

    def __iter__(self):
        return iter(self.batches)


def test_trainer_end_to_end(cfg, args, capsys):
    fast = args.replace(learning_rate=1e-3, epochs=2, dev=True, eval_step=4,
                        log_every=2)
    state, _ = _state_and_tx(cfg, fast)
    tx = build_optimizer(state["params"], fast)
    tr = Trainer(fast, cfg, state,
                 make_train_step(cfg, tx, fast), make_eval_step(cfg, fast))
    batches = [_batch(cfg, seed=i) for i in range(4)]
    minutes = tr.train(_ListLoader(batches), _ListLoader(batches[:1]))
    out = capsys.readouterr().out
    assert "【train】" in out and "耗时" in out and "【dev】" in out
    assert minutes > 0
    assert os.path.exists(fast.ckpt_path())  # best-acc checkpoint saved
    res = tr.test(_ListLoader(batches[:2]))
    assert set(res) == {"loss", "accuracy", "y_true", "y_pred"}
    assert len(res["y_true"]) == 16


def test_weighted_ce_label_smoothing():
    """The reported loss is ALWAYS the bare CE (train/dev lines stay
    comparable, mirroring the moe_aux_coef convention); the smoothed
    objective (1-eps)*NLL + eps*mean(-logp) is returned separately and
    equals the bare CE at eps=0.  Filler rows weigh 0 in both."""
    import jax
    import jax.numpy as jnp
    from pdnlp_tpu.train.steps import weighted_ce

    logits = jnp.asarray(np.random.RandomState(0).randn(8, 6), jnp.float32)
    labels = jnp.arange(8) % 6
    w = jnp.ones((8,)).at[-2:].set(0.0)
    plain, correct0, obj0 = weighted_ce(logits, labels, w)
    same, _, _ = weighted_ce(logits, labels, w, smoothing=0.0)
    assert float(plain) == float(same) == float(obj0)
    eps = 0.1
    bare, correct1, sm = weighted_ce(logits, labels, w, smoothing=eps)
    assert float(bare) == float(plain)  # reported metric ignores smoothing
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    want = ((1 - eps) * nll + eps * (-logp.mean(-1))) * w
    assert float(sm) == pytest.approx(float(want.sum() / w.sum()), rel=1e-6)
    assert float(correct0) == float(correct1)  # accuracy ignores smoothing


def test_ema_weights_tracked_and_evaluated(corpus_path, tmp_path):
    """--ema_decay: the state carries an EMA tree the step maintains
    (decay 0 -> EMA == live params exactly; 0<d<1 -> strictly between init
    and live), and eval/checkpoint read the EMA weights."""
    import jax
    import jax.numpy as jnp
    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import Args

    def flat(tree):
        return np.concatenate([np.asarray(l).ravel() for l in
                               jax.tree_util.tree_leaves(tree)])

    kw = dict(model="bert-tiny", data_limit=400, max_seq_len=16,
              train_batch_size=8, dropout=0.0, attn_dropout=0.0,
              learning_rate=1e-3, log_every=10 ** 9, data_path=corpus_path,
              vocab_path=str(tmp_path / "vocab.txt"),
              output_dir=str(tmp_path))
    tr, loader, _ = build_parallel_trainer(
        Args(strategy="ema-t", ema_decay=0.9, **kw), mode="dp")
    assert "ema" in tr.state
    init = flat(tr.state["ema"])
    for batch in loader:
        tr.state, _ = tr.train_step(tr.state, tr.put(batch))
    live, ema = flat(tr.state["params"]), flat(tr.state["ema"])
    assert not np.array_equal(ema, live)      # lags the live weights
    assert not np.array_equal(ema, init)      # but moved off init
    # between init and live in aggregate (Polyak averaging)
    assert np.linalg.norm(ema - live) < np.linalg.norm(init - live)
    # eval consumes the EMA tree
    assert tr._eval_params() is tr.state["ema"]

    tr0, loader0, _ = build_parallel_trainer(
        Args(strategy="ema-0", ema_decay=1e-9, **kw), mode="dp")
    b = next(iter(loader0))
    tr0.state, _ = tr0.train_step(tr0.state, tr0.put(b))
    np.testing.assert_allclose(flat(tr0.state["ema"]),
                               flat(tr0.state["params"]), rtol=0, atol=1e-7)

    # non-jit paths reject the knob loudly
    import pytest as _pytest
    from pdnlp_tpu.parallel import make_shardmap_train_step, make_mesh
    from pdnlp_tpu.parallel.execution import setup_sharded_model

    args = Args(strategy="ema-g", ema_decay=0.9, **kw)
    mesh = make_mesh()
    cfg, tx, _, _ = setup_sharded_model(args.replace(ema_decay=0.0),
                                        100, mesh, "dp")
    with _pytest.raises(ValueError, match="ema_decay"):
        make_shardmap_train_step(cfg, tx, args, mesh)


def test_eval_batches_uploaded_once(cfg, args):
    """The dev set is device-cached across evals: ``put`` runs once per
    distinct loader, not once per eval (the transport property the bench's
    in-loop eval cadence relies on — ``trainer._eval_cache``)."""
    state, tx = _state_and_tx(cfg, args)
    puts = []
    tr = Trainer(args, cfg, state,
                 make_train_step(cfg, tx, args), make_eval_step(cfg, args),
                 put=lambda b: puts.append(1) or b)
    dev = _ListLoader([_batch(cfg, seed=9), _batch(cfg, seed=10)])
    first = tr.dev(dev)
    assert len(puts) == 2
    assert tr.dev(dev) == first  # same params, cached device batches
    assert len(puts) == 2        # no re-upload on the second eval
    other = _ListLoader([_batch(cfg, seed=11)])
    tr.dev(other)                # a different loader replaces the cache
    assert len(puts) == 3


class _ShufflingLoader:
    """Yields a DIFFERENT batch on every iteration — the loader shape the
    identity-keyed eval cache must not silently freeze."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.iteration = 0

    def __len__(self):
        return 1

    def set_epoch(self, e):
        pass

    def __iter__(self):
        self.iteration += 1
        yield _batch(self.cfg, seed=100 + self.iteration)


def test_static_eval_false_reevaluates_fresh_batches(cfg, args):
    """``static_eval=False`` opts a shuffling/augmenting loader out of the
    identity-keyed device cache: every call re-uploads and re-evaluates the
    CURRENT iteration's batches (ADVICE round-5 item 3)."""
    state, tx = _state_and_tx(cfg, args)
    puts = []
    tr = Trainer(args, cfg, state,
                 make_train_step(cfg, tx, args), make_eval_step(cfg, args),
                 put=lambda b: puts.append(1) or b)
    loader = _ShufflingLoader(cfg)

    # default (static_eval=True): first iteration's batches are frozen
    first = tr.dev(loader)
    assert loader.iteration == 1 and len(puts) == 1
    assert tr.dev(loader) == first
    assert loader.iteration == 1 and len(puts) == 1  # cache hit: no re-pull

    # static_eval=False: the loader is re-iterated and re-uploaded
    r2 = tr.dev(loader, static_eval=False)
    assert loader.iteration == 2 and len(puts) == 2
    r3 = tr.dev(loader, static_eval=False)
    assert loader.iteration == 3 and len(puts) == 3
    assert r2 != r3              # different batches -> different metrics
    # the static cache was left untouched: a static dev() still hits it
    assert tr.dev(loader) == first and len(puts) == 3
    # test() honors the flag too
    res = tr.test(loader, static_eval=False)
    assert loader.iteration == 4 and len(puts) == 4
    assert set(res) >= {"loss", "accuracy", "y_true", "y_pred"}
