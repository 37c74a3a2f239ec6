"""Mixture-of-experts + expert parallelism ("ep") tests.

No reference twin (``SURVEY.md`` §2.3: the reference has no MoE): these
pin the framework-added capability — top-k gated expert MLPs, the Switch
load-balancing aux loss, and the ``expert`` mesh-axis sharding whose
gate-weighted combine XLA turns into the expert all-reduce.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.parallel import (
    make_global_batch, make_mesh, make_parallel_eval_step,
    make_parallel_train_step, setup_sharded_model,
)
from pdnlp_tpu.utils.config import Args

SEQ = 16
VOCAB = 100


@pytest.fixture(scope="module")
def learnable_corpus(corpus_path, tmp_path_factory):
    """The two trainer-path tests below assert that the loss FALLS, which
    takes labels a model can learn: the real corpus where it is there, else
    a synthetic one whose label is a function of its text (conftest's
    draws labels at random, which nothing can learn in two tiny epochs)."""
    import json
    import random

    if "reference" in corpus_path:
        return corpus_path
    rng = random.Random(1)
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐"
    rows = []
    for _ in range(600):
        label = rng.randint(0, 5)
        marks = chars[3 * label:3 * label + 3]   # a label's own three chars
        text = " ".join(rng.choice(marks) for _ in range(rng.randint(4, 12)))
        rows.append([text, label])
    p = tmp_path_factory.mktemp("moe_data") / "train.json"
    p.write_text(json.dumps(rows, ensure_ascii=False), encoding="utf-8")
    return str(p)


def tiny_args(**kw):
    base = dict(model="bert-tiny-moe", max_seq_len=SEQ, train_batch_size=4,
                dropout=0.0, attn_dropout=0.0)
    base.update(kw)
    return Args(**base)


def fake_batch(n, seed=0):
    r = np.random.RandomState(seed)
    return {
        "input_ids": r.randint(0, VOCAB, (n, SEQ)).astype(np.int32),
        "token_type_ids": np.zeros((n, SEQ), np.int32),
        "attention_mask": np.ones((n, SEQ), np.int32),
        "label": r.randint(0, 6, (n,)).astype(np.int32),
        "example_weight": np.ones((n,), np.float32),
    }


def test_moe_params_and_forward_shapes():
    cfg = get_config("bert-tiny-moe", vocab_size=VOCAB, num_labels=6)
    assert cfg.moe_experts == 4
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    E, L, H, I = cfg.moe_experts, cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    assert params["layers"]["up"]["kernel"].shape == (L, E, H, I)
    assert params["layers"]["down"]["kernel"].shape == (L, E, I, H)
    assert params["layers"]["gate"]["kernel"].shape == (L, H, E)

    b = fake_batch(4)
    logits, aux = bert.classify(params, cfg, b, return_aux=True)
    assert logits.shape == (4, 6)
    assert np.isfinite(np.asarray(logits)).all()
    # Switch aux: >= 1 by Cauchy-Schwarz, ~1 when balanced, summed over L
    assert float(aux) >= cfg.num_layers * 0.99


def test_moe_gating_is_topk_convex_combination():
    """With top-k = E the MoE output equals the full-softmax mixture; the
    per-token combine weights always sum to 1 over the selected experts."""
    cfg = get_config("bert-tiny-moe", vocab_size=VOCAB, num_labels=6,
                     moe_top_k=2)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, cfg.hidden_size))
    out, aux = bert.moe_mlp(x, lp, cfg)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    # top-k=E degenerates to the softmax mixture: compare against a manual
    # dense mixture with full softmax weights
    cfg_all = cfg.replace(moe_top_k=cfg.moe_experts)
    out_all, _ = bert.moe_mlp(x, lp, cfg_all)
    probs = jax.nn.softmax(
        (x @ lp["gate"]["kernel"]).astype(jnp.float32))
    up, down = lp["up"], lp["down"]
    h = jnp.einsum("bsh,ehi->ebsi", x, up["kernel"]) + up["bias"][:, None, None, :]
    y = jnp.einsum("ebsi,eih->ebsh", jax.nn.gelu(h, approximate=False),
                   down["kernel"]) + down["bias"][:, None, None, :]
    manual = jnp.einsum("ebsh,bse->bsh", y, probs)
    np.testing.assert_allclose(np.asarray(out_all), np.asarray(manual),
                               rtol=1e-5, atol=1e-5)


def test_grouped_dispatch_matches_dense():
    """The capacity-based grouped dispatch is the dense combine's equal:
    with capacity >= tokens (nothing can drop) the outputs agree to fp
    tolerance; at the shipped capacity factor the drops degrade gracefully
    (finite outputs, residual-only tokens) and a squeezed capacity changes
    outputs without breaking anything."""
    cfg_d = get_config("bert-tiny-moe", vocab_size=VOCAB, num_labels=6,
                       moe_dispatch="dense")
    params = bert.init_params(jax.random.PRNGKey(0), cfg_d)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, SEQ, cfg_d.hidden_size))

    dense_out, dense_aux = bert.moe_mlp(x, lp, cfg_d)
    # capacity >= T: no drops possible -> parity up to summation order
    cfg_full = cfg_d.replace(moe_dispatch="grouped",
                             moe_capacity_factor=float(cfg_d.moe_experts))
    full_out, full_aux = bert.moe_mlp(x, lp, cfg_full)
    np.testing.assert_allclose(np.asarray(full_out), np.asarray(dense_out),
                               rtol=2e-5, atol=2e-5)
    assert float(full_aux) == pytest.approx(float(dense_aux), rel=1e-6)

    # shipped capacity: still finite, aux identical (routing unchanged)
    cfg_g = cfg_d.replace(moe_dispatch="grouped")
    g_out, g_aux = bert.moe_mlp(x, lp, cfg_g)
    assert np.isfinite(np.asarray(g_out)).all()
    assert float(g_aux) == pytest.approx(float(dense_aux), rel=1e-6)

    # squeezed capacity drops most assignments yet stays well-formed, and
    # actually differs (the capacity knob is live)
    cfg_sq = cfg_d.replace(moe_dispatch="grouped", moe_capacity_factor=0.25)
    sq_out, _ = bert.moe_mlp(x, lp, cfg_sq)
    assert np.isfinite(np.asarray(sq_out)).all()
    assert np.abs(np.asarray(sq_out) - np.asarray(g_out)).max() > 1e-6

    # padding never occupies capacity: with a mask, fully-padded positions
    # get zero expert output (their residual carries them)
    mask = np.ones((4, SEQ), np.int32)
    mask[:, SEQ // 2:] = 0
    m_out, _ = bert.moe_mlp(x, lp, cfg_g, mask=jnp.asarray(mask))
    assert np.abs(np.asarray(m_out)[:, SEQ // 2:]).max() == 0.0
    # real positions agree with the unmasked run where no drops occurred
    assert np.isfinite(np.asarray(m_out)).all()


def test_moe_trains_and_reports_bare_ce(ndev):
    """A few steps on one device: loss decreases, and the reported metric
    is exactly the bare weighted CE — the aux loss joins the optimized
    objective only (dropout=0 makes the train forward reproducible)."""
    from pdnlp_tpu.train.steps import make_train_step, weighted_ce
    from pdnlp_tpu.train.setup import setup_model

    args = tiny_args(learning_rate=1e-3)
    cfg, tx, state = setup_model(args, VOCAB)
    params0 = jax.tree_util.tree_map(jnp.copy, state["params"])
    step = make_train_step(cfg, tx, args)
    b = fake_batch(16)
    losses = []
    for _ in range(6):
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    # recompute the bare CE on the pre-update params (dropout=0 =>
    # deterministic forward == train forward); the metric must match it,
    # NOT the CE + moe_aux_coef * aux objective
    logits, aux = bert.classify(params0, cfg, b, return_aux=True)
    bare, _, _ = weighted_ce(logits, b["label"], b["example_weight"])
    assert losses[0] == pytest.approx(float(bare), rel=1e-5)
    assert abs(losses[0] - float(bare + cfg.moe_aux_coef * aux)) > 1e-4


def test_ep_matches_dp_and_shards_experts(ndev):
    """Expert parallelism: an (data x expert) mesh reproduces the replicated
    loss/params, and each device holds 1/2 of every expert stack."""
    args = tiny_args()
    batches = [fake_batch(16, seed=s) for s in range(3)]

    mesh_dp = make_mesh(shape={"data": ndev})
    cfg, tx, st, sh = setup_sharded_model(args, VOCAB, mesh_dp, "dp")
    step = make_parallel_train_step(cfg, tx, args, mesh_dp, sh)
    put = make_global_batch(mesh_dp)
    for b in batches:
        st, m_dp = step(st, put(b))

    emesh = make_mesh(shape={"data": ndev // 2, "expert": 2})
    cfg2, tx2, st2, sh2 = setup_sharded_model(args, VOCAB, emesh, "ep")
    up = st2["params"]["layers"]["up"]["kernel"]
    assert up.addressable_shards[0].data.shape[1] == up.shape[1] // 2
    estep = make_parallel_train_step(cfg2, tx2, args, emesh, sh2)
    eput = make_global_batch(emesh)
    for b in batches:
        st2, m_ep = estep(st2, eput(b))
    assert float(m_ep["loss"]) == pytest.approx(float(m_dp["loss"]), rel=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5),
        jax.device_get(st["params"]), jax.device_get(st2["params"]))
    em = make_parallel_eval_step(cfg2, args, emesh, sh2["params"])(
        st2["params"], eput(batches[0]))
    assert float(em["weight"]) == 16.0


def test_ep_and_moe_guards(ndev):
    args = tiny_args()
    with pytest.raises(ValueError, match="expert"):
        setup_sharded_model(args, VOCAB, make_mesh(shape={"data": ndev}), "ep")
    dense = Args(model="bert-tiny", max_seq_len=SEQ, dropout=0.0,
                 attn_dropout=0.0)
    mesh = make_mesh(shape={"data": 4, "expert": 2})
    with pytest.raises(ValueError, match="MoE model"):
        setup_sharded_model(dense, VOCAB, mesh, "ep")
    # tp rejects MoE loudly (the expert dim needs ep's placement);
    # shard_map and pp now COMPOSE with MoE (aux plumbed — see
    # test_moe_on_shardmap_path / test_moe_on_pipeline_path)
    tmesh = make_mesh(shape={"data": 4, "model": 2})
    with pytest.raises(ValueError, match="ep mode"):
        setup_sharded_model(args, VOCAB, tmesh, "tp")


def test_upcycle_dense_checkpoint_into_moe(tmp_path):
    """Sparse upcycling: a DENSE pretrain checkpoint loads into an MoE
    template — every expert starts as the dense MLP (+ tiny seeded noise),
    the gate stays fresh, and the non-MLP trees copy bit-exactly."""
    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.train.pretrain import load_encoder

    dense_cfg = get_config("bert-tiny", vocab_size=VOCAB, num_labels=6)
    dense = bert.init_params(jax.random.PRNGKey(7), dense_cfg)
    path = str(tmp_path / "dense.msgpack")
    ckpt.save(path, dense)

    moe_cfg = get_config("bert-tiny-moe", vocab_size=VOCAB, num_labels=6)
    moe = bert.init_params(jax.random.PRNGKey(8), moe_cfg)
    got = load_encoder(path, moe, head=True)

    E = moe_cfg.moe_experts
    up = np.asarray(got["layers"]["up"]["kernel"])       # [L, E, H, I]
    dk = np.asarray(dense["layers"]["up"]["kernel"])     # [L, H, I]
    for e in range(E):
        diff = np.abs(up[:, e] - dk)
        assert diff.max() < 0.1 * np.abs(dk).std() + 1e-3  # close to dense
    # experts differ from EACH OTHER (symmetry broken)
    assert np.abs(up[:, 0] - up[:, 1]).max() > 0
    # biases copy exactly; gate is the fresh template init
    np.testing.assert_array_equal(
        np.asarray(got["layers"]["up"]["bias"][:, 0]),
        np.asarray(dense["layers"]["up"]["bias"]))
    np.testing.assert_array_equal(np.asarray(got["layers"]["gate"]["kernel"]),
                                  np.asarray(moe["layers"]["gate"]["kernel"]))
    # attention + LN trees copy bit-exactly; head restored under head=True
    np.testing.assert_array_equal(np.asarray(got["layers"]["q"]["kernel"]),
                                  np.asarray(dense["layers"]["q"]["kernel"]))
    np.testing.assert_array_equal(np.asarray(got["pooler"]["kernel"]),
                                  np.asarray(dense["pooler"]["kernel"]))
    # upcycled forward stays close to the dense forward (same function at
    # noise->0: every expert == the dense MLP and gating is convex)
    b = fake_batch(4)
    dense_logits = bert.classify(dense, dense_cfg, b)
    moe_logits = bert.classify(got, moe_cfg, b)
    np.testing.assert_allclose(np.asarray(moe_logits),
                               np.asarray(dense_logits), atol=0.35)


def test_moe_on_shardmap_path(ndev, learnable_corpus, tmp_path):
    """The explicit-collectives (Horovod-analog) path trains MoE: the aux
    loss is computed per shard and joins the optimized objective, while the
    REPORTED first-step loss equals the jit dp path's bare CE exactly
    (same params, same global batch, deterministic forward)."""
    from pdnlp_tpu.train.run import build_parallel_trainer

    # dense dispatch for the exact-parity comparison: grouped dispatch
    # computes capacity per CALL, so the shard_map path's shard-local slot
    # assignment legitimately differs from the jit path's global-batch one
    # (drops fall elsewhere) — only the capacity-free dense combine is
    # bitwise path-independent
    files = dict(data_path=learnable_corpus, output_dir=str(tmp_path),
                 vocab_path=str(tmp_path / "vocab.txt"))
    args = tiny_args(data_limit=600, max_seq_len=16, train_batch_size=4,
                     log_every=10 ** 9, moe_dispatch="dense", **files)
    tr_sm, loader_sm, _ = build_parallel_trainer(
        args, mode="dp", explicit_collectives=True)
    tr_dp, loader_dp, _ = build_parallel_trainer(args, mode="dp")
    b_sm = next(iter(loader_sm))
    b_dp = next(iter(loader_dp))
    np.testing.assert_array_equal(b_sm["input_ids"], b_dp["input_ids"])
    tr_sm.state, m_sm = tr_sm.train_step(tr_sm.state, tr_sm.put(b_sm))
    tr_dp.state, m_dp = tr_dp.train_step(tr_dp.state, tr_dp.put(b_dp))
    assert float(m_sm["loss"]) == pytest.approx(float(m_dp["loss"]), rel=1e-5)
    # and it actually trains
    losses = []
    tr2, loader2, _ = build_parallel_trainer(
        tiny_args(data_limit=600, max_seq_len=16, train_batch_size=4,
                  learning_rate=1e-3, log_every=10 ** 9, **files),
        mode="dp", explicit_collectives=True)
    for epoch in range(2):
        loader2.set_epoch(epoch)
        for b in loader2:
            tr2.state, m = tr2.train_step(tr2.state, tr2.put(b))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_moe_on_pipeline_path(ndev, learnable_corpus, tmp_path):
    """MoE composes with pipeline parallelism: expert stacks split their
    leading layer dim over stages and the load-balancing aux flows through
    the tick loop's backward.  Parity with dp is LOOSE here by design: a
    fresh-init gate routes near-tied experts, so program-layout-level fp
    differences can flip top-k picks — exact-parity asserts would be
    flaky.  The aux plumbing itself is pinned directly: cranking
    ``moe_aux_coef`` must change the gate update."""
    import dataclasses

    from pdnlp_tpu.train.run import build_pipeline_trainer, build_parallel_trainer
    from pdnlp_tpu.utils.config import Args

    kw = dict(model="bert-tiny-moe", max_seq_len=16, train_batch_size=4,
              dropout=0.0, attn_dropout=0.0, data_limit=600,
              learning_rate=1e-3,  # visible decrease in 2 tiny epochs
              log_every=10 ** 9, data_path=learnable_corpus,
              output_dir=str(tmp_path),
              vocab_path=str(tmp_path / "vocab.txt"))
    pp_args = Args(strategy="pp-moe", mesh_shape={"data": 4, "stage": 2},
                   microbatches=2, **kw)
    tr_pp, loader_pp, _ = build_pipeline_trainer(pp_args)
    tr_dp, loader_dp, _ = build_parallel_trainer(
        Args(strategy="dp-moe-ref", num_devices=4, **kw), mode="dp")
    b_pp = next(iter(loader_pp))
    b_dp = next(iter(loader_dp))
    np.testing.assert_array_equal(b_pp["input_ids"], b_dp["input_ids"])
    tr_pp.state, m_pp = tr_pp.train_step(tr_pp.state, tr_pp.put(b_pp))
    tr_dp.state, m_dp = tr_dp.train_step(tr_dp.state, tr_dp.put(b_dp))
    assert float(m_pp["loss"]) == pytest.approx(float(m_dp["loss"]), abs=2e-2)

    # --- the aux term genuinely reaches the pipeline's gradients: the same
    # step with a 100x aux coefficient must move the gate differently ---
    from pdnlp_tpu.models import get_config
    from pdnlp_tpu.parallel import make_mesh
    from pdnlp_tpu.parallel.pp import make_pp_train_step, setup_pp_model

    mesh = make_mesh(shape={"data": 4, "stage": 2})
    args0 = Args(strategy="pp-aux0", mesh_shape={"data": 4, "stage": 2},
                 microbatches=2, **kw)
    _, _, state_a, _ = setup_pp_model(args0, VOCAB, mesh)
    _, _, state_b, _ = setup_pp_model(args0, VOCAB, mesh)
    cfg = get_config("bert-tiny-moe", vocab_size=VOCAB, num_labels=6,
                     dropout=0.0, attn_dropout=0.0)
    from pdnlp_tpu.train.optim import build_optimizer

    tx = build_optimizer(state_a["params"], args0)
    b = fake_batch(16)
    step_lo = make_pp_train_step(
        dataclasses.replace(cfg, moe_aux_coef=0.0), tx, args0, mesh, n_micro=2)
    step_hi = make_pp_train_step(
        dataclasses.replace(cfg, moe_aux_coef=1.0), tx, args0, mesh, n_micro=2)
    state_a, m_lo = step_lo(state_a, jax.device_put(
        b, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))))
    state_b, m_hi = step_hi(state_b, jax.device_put(
        b, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))))
    # bare-CE metric identical (aux is not reported)...
    assert float(m_lo["loss"]) == pytest.approx(float(m_hi["loss"]), rel=1e-6)
    # ...but the gate update differs: aux flowed through the tick scan
    g_lo = np.asarray(state_a["params"]["layers"]["gate"]["kernel"])
    g_hi = np.asarray(state_b["params"]["layers"]["gate"]["kernel"])
    assert np.abs(g_lo - g_hi).max() > 1e-6

    # trains to a finite, decreasing loss
    losses = []
    for epoch in range(2):
        loader_pp.set_epoch(epoch)
        for b in loader_pp:
            tr_pp.state, m = tr_pp.train_step(tr_pp.state, tr_pp.put(b))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()
