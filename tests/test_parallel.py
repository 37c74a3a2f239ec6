"""Parallel-layer tests on the 8-device virtual CPU mesh.

Covers the acceptance criteria the reference only ever checked on real
hardware (``SURVEY.md`` §4): step-count math (288 single / 144 @ 2-way),
single-vs-multi-device loss parity, ZeRO memory sharding, and the explicit-
collectives (shard_map) path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.parallel import (
    local_batch_mult, make_global_batch, make_mesh, make_parallel_eval_step,
    make_parallel_train_step, make_shardmap_train_step, setup_sharded_model,
    shard_fraction,
)
from pdnlp_tpu.train.steps import make_eval_step, make_train_step
from pdnlp_tpu.utils.config import Args

SEQ = 16
VOCAB = 100


def tiny_args(**kw):
    base = dict(model="bert-tiny", max_seq_len=SEQ, train_batch_size=4,
                dropout=0.0, attn_dropout=0.0)  # 0 => math identical across layouts
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


# ----------------------------------------------------------------- mesh


def test_mesh_default_spans_all_devices(ndev):
    mesh = make_mesh()
    assert mesh.shape == {"data": ndev}


def test_mesh_shape_and_inference(ndev):
    mesh = make_mesh(shape={"data": -1, "model": 2})
    assert mesh.shape == {"data": ndev // 2, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(num_devices=ndev + 1)
    with pytest.raises(ValueError):
        make_mesh(shape={"data": ndev * 2})


def test_local_batch_mult_single_process(ndev):
    assert local_batch_mult(make_mesh()) == ndev
    assert local_batch_mult(make_mesh(num_devices=2)) == 2


def test_step_math_144_at_2way(corpus_path):
    """Global batch 64 at 2-way DP over the 9,200-example split -> 144 steps
    (the reference's DistributedSampler math, SURVEY.md §6)."""
    from pdnlp_tpu.train.setup import setup_data

    args = Args(data_path=corpus_path, vocab_path="output/test_vocab_parallel.txt")
    train_loader, _, _ = setup_data(args, device_batch_mult=2)
    n = len(train_loader.sampler)
    assert len(train_loader) == -(-n // 64)
    if n == 9200:  # real corpus present
        assert len(train_loader) == 144


# ------------------------------------------------------- batch assembly


def test_make_global_batch_roundtrip(ndev):
    mesh = make_mesh()
    put = make_global_batch(mesh)
    b = fake_batch(ndev * 2)
    g = put(b)
    for k, v in b.items():
        assert g[k].shape == v.shape
        np.testing.assert_array_equal(np.asarray(g[k]), v)
        # sharded along data: each device holds 2 rows
        assert g[k].addressable_shards[0].data.shape[0] == 2


# ------------------------------------------------------------ parity


def single_device_reference(args, batch):
    """Train one step + eval on device 0 only (the single-GPU baseline)."""
    from pdnlp_tpu.train.setup import setup_model

    cfg, tx, state = setup_model(args, VOCAB)
    step = make_train_step(cfg, tx, args)
    ev = make_eval_step(cfg, args)
    state, m = step(state, batch)
    em = ev(state["params"], batch)
    return float(m["loss"]), float(em["correct"]), state


@pytest.mark.parametrize("mode", ["dp", "zero"])
def test_parallel_loss_matches_single_device(mode, ndev):
    """The north-star correctness check: the same global batch through the
    mesh gives the same loss/metrics as one device."""
    args = tiny_args()
    batch = fake_batch(32)
    ref_loss, ref_correct, ref_state = single_device_reference(args, batch)

    mesh = make_mesh()
    cfg, tx, state, sh = setup_sharded_model(args, VOCAB, mesh, mode)
    step = make_parallel_train_step(cfg, tx, args, mesh, sh)
    ev = make_parallel_eval_step(cfg, args, mesh, sh["params"])
    put = make_global_batch(mesh)
    state, m = step(state, put(batch))
    em = ev(state["params"], put(batch))

    assert float(m["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    assert float(em["correct"]) == pytest.approx(ref_correct, abs=1.0)
    # params after one update agree leafwise
    ref_leaves = jax.tree_util.tree_leaves(ref_state["params"])
    par_leaves = jax.tree_util.tree_leaves(state["params"])
    for a, b in zip(ref_leaves, par_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_tp_matches_dp_and_shards_layers(ndev):
    """Tensor parallelism (no reference twin): a (data x model) mesh with
    Megatron-sharded layer weights reproduces the dp loss and params, and
    each device really holds a fraction of every layer kernel."""
    args = tiny_args()
    batches = [fake_batch(16, seed=s) for s in range(3)]

    mesh_dp = make_mesh(shape={"data": ndev})
    cfg, tx, st, sh = setup_sharded_model(args, VOCAB, mesh_dp, "dp")
    step = make_parallel_train_step(cfg, tx, args, mesh_dp, sh)
    put = make_global_batch(mesh_dp)
    for b in batches:
        st, m_dp = step(st, put(b))

    mesh_tp = make_mesh(shape={"data": ndev // 2, "model": 2})
    cfg2, tx2, st2, sh2 = setup_sharded_model(args, VOCAB, mesh_tp, "tp")
    # layer kernels are feature-sharded: a device holds 1/2 of each
    q = st2["params"]["layers"]["q"]["kernel"]
    assert q.addressable_shards[0].data.shape[-1] == q.shape[-1] // 2
    down = st2["params"]["layers"]["down"]["kernel"]
    assert down.addressable_shards[0].data.shape[1] == down.shape[1] // 2
    # the Adam moments mirror the placement (the name rule rides the path)
    step2 = make_parallel_train_step(cfg2, tx2, args, mesh_tp, sh2)
    ev2 = make_parallel_eval_step(cfg2, args, mesh_tp, sh2["params"])
    put2 = make_global_batch(mesh_tp)
    for b in batches:
        st2, m_tp = step2(st2, put2(b))
    assert float(m_tp["loss"]) == pytest.approx(float(m_dp["loss"]), rel=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5),
        jax.device_get(st["params"]), jax.device_get(st2["params"]))
    em = ev2(st2["params"], put2(batches[0]))
    assert float(em["weight"]) == 16.0


def test_tp_rejects_bad_degree_and_missing_axis(ndev):
    args = tiny_args()
    with pytest.raises(ValueError, match="model"):
        setup_sharded_model(args, VOCAB, make_mesh(shape={"data": ndev}), "tp")
    # bert-tiny has 2 heads: degree 4 cannot split them
    mesh = make_mesh(shape={"data": 2, "model": 4})
    with pytest.raises(ValueError, match="num_heads"):
        setup_sharded_model(args, VOCAB, mesh, "tp")


def test_pp_matches_dp_and_shards_stages(ndev):
    """Pipeline parallelism (no reference twin): GPipe microbatching over a
    'stage' mesh axis reproduces the dp loss/params, each stage holds its
    slice of the layer stack, and the eval step keeps the metric contract."""
    from pdnlp_tpu.parallel.pp import (
        make_pp_batch, make_pp_eval_step, make_pp_train_step, setup_pp_model,
    )

    args = tiny_args()
    batches = [fake_batch(16, seed=s) for s in range(3)]

    mesh_dp = make_mesh(shape={"data": ndev})
    cfg, tx, st, sh = setup_sharded_model(args, VOCAB, mesh_dp, "dp")
    step = make_parallel_train_step(cfg, tx, args, mesh_dp, sh)
    put = make_global_batch(mesh_dp)
    for b in batches:
        st, m_dp = step(st, put(b))

    pmesh = make_mesh(shape={"stage": 2})  # bert-tiny: 2 layers, 1 per stage
    cfg2, tx2, st2, _ = setup_pp_model(args, VOCAB, pmesh)
    q = st2["params"]["layers"]["q"]["kernel"]
    assert q.addressable_shards[0].data.shape[0] == q.shape[0] // 2
    pstep = make_pp_train_step(cfg2, tx2, args, pmesh, n_micro=4)
    pput = make_pp_batch(pmesh)
    for b in batches:
        st2, m_pp = pstep(st2, pput(b))
    assert float(m_pp["loss"]) == pytest.approx(float(m_dp["loss"]), rel=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5),
        jax.device_get(st["params"]), jax.device_get(st2["params"]))

    ev = make_pp_eval_step(cfg2, args, pmesh, n_micro=4)
    em = ev(st2["params"], pput(batches[0]))
    assert float(em["weight"]) == 16.0
    assert em["pred"].shape == (16,)

    # dp x pp composition: each data shard runs its own pipeline; a ragged
    # batch (filler rows weigh 0) keeps the weighted grad combine exact
    ragged = fake_batch(16, seed=7)
    ragged["example_weight"][-3:] = 0.0
    st_dp2 = st
    for b in (ragged,):
        st_dp2, m_dp2 = step(st_dp2, put(b))
    cmesh = make_mesh(shape={"data": 2, "stage": 2})
    cfg3, tx3, st3, _ = setup_pp_model(args, VOCAB, cmesh)
    cstep = make_pp_train_step(cfg3, tx3, args, cmesh, n_micro=2)
    cput = make_pp_batch(cmesh)
    for b in batches + [ragged]:
        st3, m_c = cstep(st3, cput(b))
    assert float(m_c["loss"]) == pytest.approx(float(m_dp2["loss"]), rel=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5),  # 4 Adam steps of drift
        jax.device_get(st_dp2["params"]), jax.device_get(st3["params"]))
    cem = make_pp_eval_step(cfg3, args, cmesh, n_micro=2)(
        st3["params"], cput(ragged))
    assert float(cem["weight"]) == 13.0
    assert np.asarray(cem["pred"]).shape == (16,)

    # dropout on: its own stream, but the pipeline must stay finite
    dr_args = tiny_args(dropout=0.1, attn_dropout=0.1)
    cfg3, tx3, st3, _ = setup_pp_model(dr_args, VOCAB, pmesh)
    dstep = make_pp_train_step(cfg3, tx3, dr_args, pmesh, n_micro=2)
    st3, m3 = dstep(st3, pput(batches[0]))
    assert np.isfinite(float(m3["loss"]))


def test_pp_rejects_bad_degree_and_missing_axis(ndev):
    from pdnlp_tpu.parallel.pp import setup_pp_model

    args = tiny_args()
    with pytest.raises(ValueError, match="stage"):
        setup_pp_model(args, VOCAB, make_mesh(shape={"data": ndev}))
    # bert-tiny has 2 layers: 2 stages is the ceiling
    with pytest.raises(ValueError, match="num_layers"):
        setup_pp_model(args, VOCAB, make_mesh(shape={"stage": 4}))


def test_zero_shards_state_memory(ndev):
    args = tiny_args()
    mesh = make_mesh()
    _, _, dp_state, _ = setup_sharded_model(args, VOCAB, mesh, "dp")
    _, _, zero_state, _ = setup_sharded_model(args, VOCAB, mesh, "zero")
    assert shard_fraction(dp_state, mesh) == pytest.approx(1.0)
    # nearly all bytes are shardable float leaves -> ~1/ndev per device
    assert shard_fraction(zero_state, mesh) < 1.5 / ndev


def test_offload_opt_state_matches_dp(ndev):
    """--offload_opt_state (DeepSpeed offload_optimizer analog): Adam
    moments live in pinned host memory, the step stages them explicitly,
    and three updates produce the same params as the on-device run.

    TPU-only: XLA:CPU has no implementation of the memory-space
    annotation custom-call ("No registered implementation ... for Host"),
    so this executes on the real chip (where scripts/probe_offload.py
    measured it at ~4x step cost) and skips in the CPU CI mesh — the
    placement/flag plumbing still runs here up to the compile."""
    def float_kinds(opt_state):
        return {l.sharding.memory_kind
                for l in jax.tree_util.tree_leaves(opt_state)
                if isinstance(l, jax.Array)
                and jnp.issubdtype(l.dtype, jnp.floating)}

    if jax.default_backend() != "tpu":
        off_args = tiny_args(offload_opt_state=True)
        mesh = make_mesh(num_devices=1)
        _, _, state, _ = setup_sharded_model(off_args, VOCAB, mesh, "dp")
        assert float_kinds(state["opt_state"]) == {"pinned_host"}
        pytest.skip("XLA:CPU lacks annotate_device_placement; the staged "
                    "step itself is TPU-only (probe-measured)")
    args = tiny_args()
    batches = [fake_batch(8, seed=i) for i in range(3)]
    mesh = make_mesh(num_devices=1)
    put = make_global_batch(mesh)

    cfg, tx, ref_state, ref_sh = setup_sharded_model(args, VOCAB, mesh, "dp")
    ref_step = make_parallel_train_step(cfg, tx, args, mesh, ref_sh)
    for b in batches:
        ref_state, ref_m = ref_step(ref_state, put(b))

    off_args = tiny_args(offload_opt_state=True)
    cfg2, tx2, state, sh = setup_sharded_model(off_args, VOCAB, mesh, "dp")
    # the moments (all the bytes) really are host-resident
    assert float_kinds(state["opt_state"]) == {"pinned_host"}
    step = make_parallel_train_step(cfg2, tx2, off_args, mesh, sh)
    for b in batches:
        state, m = step(state, put(b))
    assert float_kinds(state["opt_state"]) == {"pinned_host"}
    assert float(m["loss"]) == pytest.approx(float(ref_m["loss"]), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                    jax.tree_util.tree_leaves(state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_shardmap_matches_dp(ndev):
    """Explicit-collective (Horovod-analog) step == XLA-inserted collectives,
    with dropout off and bf16 wire compression disabled."""
    args = tiny_args()
    batch = fake_batch(32)
    mesh = make_mesh()

    cfg, tx, state, sh = setup_sharded_model(args, VOCAB, mesh, "dp")
    put = make_global_batch(mesh)
    dp_step = make_parallel_train_step(cfg, tx, args, mesh, sh)
    dp_state, dp_m = dp_step(state, put(batch))

    _, _, state2, _ = setup_sharded_model(args, VOCAB, mesh, "dp")
    sm_step = make_shardmap_train_step(cfg, tx, args, mesh, compress_grads=False)
    sm_state, sm_m = sm_step(state2, put(batch))

    assert float(sm_m["loss"]) == pytest.approx(float(dp_m["loss"]), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(dp_state["params"]),
                    jax.tree_util.tree_leaves(sm_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_shardmap_bf16_compression_close(ndev):
    """bf16 gradient compression (the hvd.Compression.fp16 analog) stays
    close to the uncompressed update but is not bitwise identical."""
    args = tiny_args()
    batch = fake_batch(32)
    mesh = make_mesh()
    cfg, tx, state, sh = setup_sharded_model(args, VOCAB, mesh, "dp")
    put = make_global_batch(mesh)
    sm = make_shardmap_train_step(cfg, tx, args, mesh, compress_grads=True)
    _, m = sm(state, put(batch))
    _, _, state2, _ = setup_sharded_model(args, VOCAB, mesh, "dp")
    dp = make_parallel_train_step(cfg, tx, args, mesh, sh)
    _, m2 = dp(state2, put(batch))
    assert float(m["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)


# --------------------------------------------------------------- eval


def test_eval_echoes_global_labels(ndev):
    """Eval returns labels/weights through the device (replicated), so every
    host can build the classification report from global predictions."""
    args = tiny_args()
    batch = fake_batch(32)
    mesh = make_mesh()
    cfg, _, state, sh = setup_sharded_model(args, VOCAB, mesh, "dp")
    ev = make_parallel_eval_step(cfg, args, mesh, sh["params"])
    m = ev(state["params"], make_global_batch(mesh)(batch))
    np.testing.assert_array_equal(np.asarray(m["label"]), batch["label"])
    np.testing.assert_array_equal(np.asarray(m["ew"]), batch["example_weight"])
    assert m["pred"].shape == (32,)
