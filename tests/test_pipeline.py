"""Input-pipeline tests (``pdnlp_tpu.data.pipeline``).

The acceptance bars of the device-resident pipeline are *bitwise*, not
approximate: identical batches, identical per-step loss sequences over
multiple epochs, identical continuation after a mid-epoch resume — with
ZERO steady-state in-loop host->device uploads.  The prefetch pipeline is
pinned to its overlap contract (at most one batch in flight) and to loud
failure (exceptions in ``put`` propagate).
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.data import Collator, DataLoader, WordPieceTokenizer, build_vocab
from pdnlp_tpu.data.collate import EncodedDataset
from pdnlp_tpu.data.pipeline import (
    DevicePrefetchPipeline, DeviceResidentPipeline, SyncPipeline,
    _MacroStage, build_pipeline, host_macro_batches,
)
from pdnlp_tpu.data.sampler import DistributedShardSampler
from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.train import Trainer, build_optimizer, init_state, make_train_step
from pdnlp_tpu.train.steps import make_multi_step
from pdnlp_tpu.train.trainer import LoopHooks
from pdnlp_tpu.utils.config import Args

SEQ = 16
BATCH = 8


@pytest.fixture(scope="module")
def corpus():
    """Tiny deterministic (text, label) corpus — no real data needed."""
    rng = np.random.RandomState(7)
    chars = "天地人你我他好大小上下来去爱乐高兴悲伤"
    # 118 examples: the last 8-row chunk holds 6 real rows + 2 filler, so
    # the padding/masking path is inside every parity assertion
    return [("".join(rng.choice(list(chars))
                     for _ in range(int(rng.randint(4, SEQ + 4)))),
             int(rng.randint(0, 6))) for _ in range(118)]


@pytest.fixture(scope="module")
def tok(corpus):
    return WordPieceTokenizer(build_vocab((t for t, _ in corpus), size=256))


def make_loader(corpus, tok, shuffle=True, encoded=True, prefetch=0):
    col = Collator(tok, max_seq_len=SEQ)
    enc = EncodedDataset(corpus, tok, max_seq_len=SEQ) if encoded else None
    return DataLoader(
        corpus, col, BATCH,
        sampler=DistributedShardSampler(len(corpus), shuffle=shuffle, seed=5),
        prefetch=prefetch, encoded=enc)


def fetch(batch):
    return {k: np.asarray(jax.device_get(v)) for k, v in batch.items()}


# ----------------------------------------------------------- data parity

def test_resident_batches_bitwise_equal_host_loader(corpus, tok):
    """Resident gathers == host loader batches, key for key, 2 epochs."""
    loader = make_loader(corpus, tok)
    pipe = DeviceResidentPipeline(make_loader(corpus, tok))
    for epoch in range(2):
        loader.set_epoch(epoch)
        pipe.set_epoch(epoch)
        host = list(loader)
        dev = list(pipe.macro_batches(1))
        assert len(dev) == len(host) == len(loader)
        for hb, (db, n, fused, ex) in zip(host, dev):
            assert (n, fused) == (1, False)
            assert ex == int(hb["example_weight"].sum())
            got = fetch(db)
            assert set(got) == set(hb)
            for k in hb:
                np.testing.assert_array_equal(got[k], hb[k], err_msg=k)
    # ZERO steady-state uploads: only the one-time residency + per-epoch
    # indices crossed to the device
    snap = pipe.stats.snapshot()
    assert snap["puts_in_loop"] == 0
    assert snap["bytes_uploaded_in_loop"] == 0
    assert snap["bytes_per_step"] == 0.0
    assert snap["bytes_uploaded_total"] > 0       # residency was measured
    assert snap["steps"] == 2 * len(loader)


def test_resident_fused_groups_match_host_stacking(corpus, tok):
    """fuse_steps=K: [K, B, ...] gathers == the host macro-stack, with the
    remainder yielded as singles."""
    k = 3
    loader = make_loader(corpus, tok)
    pipe = DeviceResidentPipeline(make_loader(corpus, tok))
    loader.set_epoch(0)
    pipe.set_epoch(0)
    # consume the host stream incrementally: fused host groups live in a
    # reused staging buffer, valid only until the next iteration
    dev_iter = pipe.macro_batches(k)
    shapes = []
    for hb, hn, hfused, hex_ in host_macro_batches(loader, k):
        db, dn, dfused, dex = next(dev_iter)
        assert (hn, hfused, hex_) == (dn, dfused, dex)
        shapes.append((hn, hfused))
        got = fetch(db)
        for key in hb:
            np.testing.assert_array_equal(got[key], hb[key], err_msg=key)
    assert next(dev_iter, None) is None
    n_chunks = len(loader)
    assert shapes == [(k, True)] * (n_chunks // k) + \
        [(1, False)] * (n_chunks % k)


# ----------------------------------------------------- training parity

def _trainer(args, cfg, tok, pipeline=None, fuse=False):
    params = bert.init_params(jax.random.key(0), cfg)
    tx = build_optimizer(params, args)
    state = init_state(jax.random.key(0), cfg, tx, rng=jax.random.key(1))
    return Trainer(args, cfg, state, make_train_step(cfg, tx, args),
                   eval_step=None,
                   multi_step=make_multi_step(cfg, tx, args) if fuse else None,
                   pipeline=pipeline)


def _losses_of(trainer, loader, args):
    seen = []
    hooks = LoopHooks(on_log=lambda e, s, t, l: seen.append((s, l)),
                      end_save=False)
    trainer.train(loader, None, hooks=hooks)
    return seen


def test_resident_training_bitwise_parity_and_resume(corpus, tok, tmp_path):
    """THE acceptance test: per-step losses over 2 epochs are IDENTICAL
    between the host (sync put) path and the device-resident pipeline —
    and stay identical after a mid-epoch save/restore fast-forward."""
    args = Args(model="bert-tiny", output_dir=str(tmp_path), epochs=2,
                train_batch_size=BATCH, max_seq_len=SEQ, learning_rate=1e-3,
                log_every=1, dev=False)
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)

    host_tr = _trainer(args, cfg, tok)
    host_losses = _losses_of(host_tr, make_loader(corpus, tok), args)

    res_loader = make_loader(corpus, tok)
    res_tr = _trainer(args, cfg, tok,
                      pipeline=DeviceResidentPipeline(res_loader))
    res_losses = _losses_of(res_tr, res_loader, args)

    assert len(host_losses) == len(res_losses) > 0
    assert [s for s, _ in host_losses] == [s for s, _ in res_losses]
    np.testing.assert_array_equal([l for _, l in host_losses],
                                  [l for _, l in res_losses])
    assert res_tr.pipeline.stats.snapshot()["bytes_uploaded_in_loop"] == 0

    # mid-epoch resume: save at a step inside epoch 1, restore into a FRESH
    # resident-pipeline trainer, fast-forward, finish — tail must match
    steps_per_epoch = len(res_loader)
    cut = steps_per_epoch + 3  # strictly inside epoch 2
    half_tr = _trainer(args, cfg, tok)
    seen = []

    def stop_at_cut(e, s, t, l):
        seen.append((s, l))

    hooks = LoopHooks(on_log=stop_at_cut, end_save=False)
    one = args.replace(epochs=1)
    half_tr.args = one
    half_tr.train(make_loader(corpus, tok), None, hooks=hooks)
    # continue 3 steps into epoch 2 manually to land mid-epoch
    l2 = make_loader(corpus, tok)
    l2.set_epoch(1)
    it = iter(l2)
    for _ in range(3):
        half_tr.state, _ = half_tr.train_step(half_tr.state,
                                              next(it))
    snap = str(tmp_path / "mid.msgpack")
    half_tr.save_resume(snap)
    assert int(jax.device_get(half_tr.state["step"])) == cut

    cont_loader = make_loader(corpus, tok)
    cont_tr = _trainer(args, cfg, tok,
                       pipeline=DeviceResidentPipeline(cont_loader))
    cont_tr.load_resume(snap)
    cont_losses = _losses_of(cont_tr, cont_loader, args)
    tail = {s: l for s, l in host_losses if s > cut}
    got = {s: l for s, l in cont_losses}
    assert set(tail) <= set(got)
    np.testing.assert_array_equal([tail[s] for s in sorted(tail)],
                                  [got[s] for s in sorted(tail)])


def test_resident_fused_training_matches_host_fused(corpus, tok, tmp_path):
    """fuse_steps=2 through multi_step: resident vs host fused losses."""
    args = Args(model="bert-tiny", output_dir=str(tmp_path), epochs=1,
                train_batch_size=BATCH, max_seq_len=SEQ, learning_rate=1e-3,
                fuse_steps=2, log_every=1, dev=False)
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    host_tr = _trainer(args, cfg, tok, fuse=True)
    host_losses = _losses_of(host_tr, make_loader(corpus, tok), args)
    res_loader = make_loader(corpus, tok)
    res_tr = _trainer(args, cfg, tok, fuse=True,
                      pipeline=DeviceResidentPipeline(res_loader))
    res_losses = _losses_of(res_tr, res_loader, args)
    np.testing.assert_array_equal([l for _, l in host_losses],
                                  [l for _, l in res_losses])


# ------------------------------------------------------------- prefetch

def test_prefetch_at_most_one_batch_in_flight(corpus, tok):
    """The double-buffer contract: the worker never runs ahead by more
    than ONE uploaded-but-undelivered batch (the 1-slot semaphore makes
    ``puts <= consumed + 1`` an invariant, not a race), and it DOES run
    ahead — the put for k+1 lands while the consumer still holds k."""
    import time as _t

    puts = [0]
    lock = threading.Lock()

    def put(b):
        with lock:
            puts[0] += 1
        return b

    pipe = DevicePrefetchPipeline(make_loader(corpus, tok), put=put)
    consumed = 0
    leads = []
    for batch, _, _, _ in pipe.macro_batches(1):
        consumed += 1
        _t.sleep(0.01)  # let the worker upload the next batch meanwhile
        with lock:
            leads.append(puts[0] - consumed)
    assert consumed == len(pipe.loader)
    assert pipe.stats.in_flight_max == 1
    assert max(leads) <= 1   # bounded: never more than one ahead
    assert max(leads) == 1   # overlap: it did upload ahead at least once


def test_prefetch_put_exception_propagates(corpus, tok):
    calls = {"n": 0}

    def bad_put(b):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("link down")
        return b

    pipe = DevicePrefetchPipeline(make_loader(corpus, tok), put=bad_put)
    with pytest.raises(RuntimeError, match="link down"):
        list(pipe.macro_batches(1))


def test_prefetch_abandonment_stops_worker(corpus, tok):
    before = threading.active_count()
    pipe = DevicePrefetchPipeline(make_loader(corpus, tok))
    gen = pipe.macro_batches(1)
    next(gen)
    gen.close()  # mid-epoch break: one bounded join, no strand
    assert threading.active_count() <= before


def test_prefetch_losses_match_sync(corpus, tok, tmp_path):
    args = Args(model="bert-tiny", output_dir=str(tmp_path), epochs=1,
                train_batch_size=BATCH, max_seq_len=SEQ, learning_rate=1e-3,
                log_every=1, dev=False)
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    sync_loader = make_loader(corpus, tok)
    sync_tr = _trainer(args, cfg, tok, pipeline=SyncPipeline(sync_loader))
    a = _losses_of(sync_tr, sync_loader, args)
    pre_loader = make_loader(corpus, tok)
    pre_tr = _trainer(args, cfg, tok,
                      pipeline=DevicePrefetchPipeline(pre_loader))
    b = _losses_of(pre_tr, pre_loader, args)
    np.testing.assert_array_equal([l for _, l in a], [l for _, l in b])


# ------------------------------------------------------- mode selection

def test_build_pipeline_auto_and_refusals(corpus, tok):
    args = Args()
    # eligible: resident
    assert isinstance(build_pipeline(args, make_loader(corpus, tok)),
                      DeviceResidentPipeline)
    # no EncodedDataset (collator could shuffle/augment): refused
    plain = make_loader(corpus, tok, encoded=False)
    assert isinstance(build_pipeline(args, plain), DevicePrefetchPipeline)
    with pytest.raises(ValueError, match="EncodedDataset"):
        build_pipeline(args.replace(pipeline="resident"), plain)
    # over the HBM budget: refused
    tiny = args.replace(pipeline_hbm_mb=0)
    assert isinstance(build_pipeline(tiny, make_loader(corpus, tok)),
                      DevicePrefetchPipeline)
    with pytest.raises(ValueError, match="budget"):
        build_pipeline(tiny.replace(pipeline="resident"),
                       make_loader(corpus, tok))
    # custom batch placement (sp/pp): refused
    with pytest.raises(ValueError, match="placement"):
        build_pipeline(args.replace(pipeline="resident"),
                       make_loader(corpus, tok), allow_resident=False)
    # explicit sync
    assert isinstance(build_pipeline(args.replace(pipeline="sync"),
                                     make_loader(corpus, tok)), SyncPipeline)
    with pytest.raises(ValueError, match="unknown pipeline"):
        build_pipeline(args.replace(pipeline="nope"),
                       make_loader(corpus, tok))


# ------------------------------------------------------- mesh resident

def test_resident_on_mesh_matches_host_put(corpus, tok, ndev):
    """Sharded gather: on the 8-device CPU mesh, resident batches (dataset
    replicated or row-sharded, output sharded along 'data') feed the same
    compiled step to the same losses as host batches through
    ``make_global_batch``."""
    from pdnlp_tpu.parallel import (
        make_global_batch, make_mesh, make_parallel_train_step,
        setup_sharded_model,
    )

    args = Args(model="bert-tiny", train_batch_size=BATCH, max_seq_len=SEQ,
                learning_rate=1e-3)
    mesh = make_mesh()
    cfg, tx, state_a, sh = setup_sharded_model(args, tok.vocab_size, mesh,
                                               "dp")
    step = make_parallel_train_step(cfg, tx, args, mesh, sh)
    put = make_global_batch(mesh)

    loader = make_loader(corpus, tok)
    loader.set_epoch(0)
    host_losses = []
    for b in loader:
        state_a, m = step(state_a, put(b))
        host_losses.append(float(m["loss"]))

    _, _, state_b, _ = setup_sharded_model(args, tok.vocab_size, mesh, "dp")
    res_loader = make_loader(corpus, tok)
    pipe = DeviceResidentPipeline(res_loader, mesh=mesh)
    pipe.set_epoch(0)
    res_losses = []
    for batch, _, _, _ in pipe.macro_batches(1):
        state_b, m = step(state_b, batch)
        res_losses.append(float(m["loss"]))
    np.testing.assert_array_equal(host_losses, res_losses)
    assert pipe.stats.snapshot()["bytes_uploaded_in_loop"] == 0


# ------------------------------------------------- macro-batch staging

def test_macro_stage_reuses_buffers_with_copying_put(corpus, tok):
    """With a copying upload, fused groups reuse the two preallocated
    ping-pong buffers instead of fresh np.stack allocations."""
    loader = make_loader(corpus, tok)
    stage = _MacroStage(2)
    ids = []
    for batch, n, fused, _ in host_macro_batches(loader, 2, stage):
        if fused:
            dev = {k: np.copy(v) for k, v in batch.items()}  # copying put
            stage.verify(batch, dev)
            ids.append(id(batch["input_ids"]))
    assert len(ids) >= 3
    assert stage.enabled
    assert len(set(ids)) == 2          # ping-pong pair, reused
    assert ids[0] == ids[2]            # alternation


def test_macro_stage_disables_on_aliased_upload(corpus, tok):
    """An identity put aliases the staging buffer into the 'uploaded'
    batch; the guard must detect it and fall back to fresh stacks."""
    loader = make_loader(corpus, tok)
    stage = _MacroStage(2)
    prev = None
    for batch, n, fused, _ in host_macro_batches(loader, 2, stage):
        if fused:
            stage.verify(batch, batch)  # identity put: aliased
            if prev is not None:
                held, snapshot = prev
                # the previously-yielded group was NOT overwritten: after
                # the guard trips, every group gets fresh memory
                np.testing.assert_array_equal(held, snapshot)
            prev = (batch["input_ids"], batch["input_ids"].copy())
    assert not stage.enabled
    assert not stage._bufs             # staging memory released


def test_trainer_classic_path_still_macro_stacks(corpus, tok, tmp_path):
    """No pipeline: the Trainer's internal staging path yields the same
    stream the old per-group np.stack produced (consumed incrementally —
    a fused group is only valid until the next iteration)."""
    args = Args(model="bert-tiny", output_dir=str(tmp_path), epochs=1,
                train_batch_size=BATCH, max_seq_len=SEQ, fuse_steps=2,
                learning_rate=1e-3, log_every=1, dev=False)
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    tr = _trainer(args, cfg, tok, fuse=True)
    loader = make_loader(corpus, tok)
    loader.set_epoch(0)
    plain = list(loader)
    loader.set_epoch(0)
    i = steps = 0
    for batch, n, fused, ex in tr._macro_batches(loader, 2):
        steps += n
        group = plain[i: i + n]
        if fused:
            for j, pb in enumerate(group):
                for key in pb:
                    np.testing.assert_array_equal(batch[key][j], pb[key],
                                                  err_msg=key)
        else:
            for key in group[0]:
                np.testing.assert_array_equal(batch[key], group[0][key])
        i += n
    assert steps == len(plain)
