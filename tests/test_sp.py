"""Sequence-parallel (ring attention) tests on the 8-device CPU mesh.

The acceptance bar: a (data x seq) mesh step must reproduce the
single-device forward/backward exactly (dropout off), and ring attention
alone must equal full attention for sharded Q/KV."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pdnlp_tpu.parallel import make_mesh
from pdnlp_tpu.parallel.sp import make_sp_batch, make_sp_eval_step, make_sp_train_step
from pdnlp_tpu.train.setup import setup_model
from pdnlp_tpu.train.steps import make_eval_step, make_train_step
from pdnlp_tpu.utils.config import Args

S, V = 32, 100


def sp_args(**kw):
    base = dict(model="bert-tiny", max_seq_len=S, dropout=0.0, attn_dropout=0.0)
    base.update(kw)
    return Args(**base)


def make_batch(n=16, seed=0, seq=S, full_mask=False):
    r = np.random.RandomState(seed)
    b = {
        "input_ids": r.randint(0, V, (n, seq)).astype(np.int32),
        "token_type_ids": np.zeros((n, seq), np.int32),
        "attention_mask": (np.ones((n, seq)) if full_mask
                           else (r.rand(n, seq) > 0.1)).astype(np.int32),
        "label": r.randint(0, 6, (n,)).astype(np.int32),
        "example_weight": np.ones((n,), np.float32),
    }
    b["attention_mask"][:, 0] = 1  # [CLS] always visible
    return b


def test_ring_attention_matches_full(ndev):
    """ring_attention over a seq-sharded layout == XLA attention, including
    mask bias, for both output rows and gradients."""
    from pdnlp_tpu.ops.attention import dot_product_attention, mask_bias
    from pdnlp_tpu.ops.ring import ring_attention

    mesh = make_mesh(shape={"seq": ndev})
    B, Sq, N, D = 2, 8 * ndev, 2, 16
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    k = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    v = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    mask = jnp.asarray((r.rand(B, Sq) > 0.2).astype(np.int32)).at[:, 0].set(1)
    bias_add = (1.0 - mask.astype(jnp.float32)) * -1e9

    ref = dot_product_attention(q, k, v, mask_bias(mask), impl="xla")

    ringed = jax.jit(shard_map(
        lambda q, k, v, b: ring_attention(q, k, v, b, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    ))(q, k, v, bias_add)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(ref), atol=2e-5)

    # gradients through the ring (ppermute backward) match too
    g_ref = jax.grad(lambda q: (dot_product_attention(
        q, k, v, mask_bias(mask), impl="xla") ** 2).sum())(q)
    g_ring = jax.grad(lambda q: (shard_map(
        lambda q, k, v, b: ring_attention(q, k, v, b, axis_name="seq"),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 4,
        out_specs=P(None, "seq"),
        check_vma=False,
    )(q, k, v, bias_add) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=5e-5)


def test_ring_attention_dropout(ndev):
    """Attention-probability dropout inside the ring: no key is a no-op,
    a key changes the output reproducibly, and the mean over many keys
    converges to the undropped output (the numerator-masked online softmax
    is unbiased — ``ops.ring._block_attn`` docstring)."""
    from pdnlp_tpu.ops.ring import ring_attention

    mesh = make_mesh(shape={"seq": ndev})
    B, Sq, N, D = 2, 4 * ndev, 2, 8
    r = np.random.RandomState(3)
    q = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    k = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    v = jnp.asarray(r.randn(B, Sq, N, D), jnp.float32)
    zbias = jnp.zeros((B, Sq), jnp.float32)

    def make_run(rate, with_key):
        def inner(q, k, v, b, seed):
            key = jax.random.key(seed[0]) if with_key else None
            return ring_attention(q, k, v, b, axis_name="seq",
                                  dropout_rate=rate, dropout_rng=key)

        return jax.jit(shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, "seq"),) * 4 + (P(),),
            out_specs=P(None, "seq"),
            check_vma=False,
        ))

    def seed(i):
        return jnp.asarray([i], jnp.uint32)

    base = np.asarray(make_run(0.0, False)(q, k, v, zbias, seed(0)))
    # rate > 0 without a key, and a key with rate 0, are both no-ops
    np.testing.assert_array_equal(
        np.asarray(make_run(0.3, False)(q, k, v, zbias, seed(0))), base)
    np.testing.assert_array_equal(
        np.asarray(make_run(0.0, True)(q, k, v, zbias, seed(0))), base)

    drop = make_run(0.3, True)
    a = np.asarray(drop(q, k, v, zbias, seed(1)))
    assert not np.allclose(a, base, atol=1e-3)
    np.testing.assert_array_equal(a, np.asarray(drop(q, k, v, zbias, seed(1))))

    # unbiasedness: E[dropout(softmax) @ v] == softmax @ v (fixed seeds, so
    # the tolerance is a one-time calibration, not a flake source)
    acc = np.zeros_like(base)
    K = 400
    for i in range(K):
        acc += np.asarray(drop(q, k, v, zbias, seed(100 + i)))
    np.testing.assert_allclose(acc / K, base, atol=0.12)


def test_sp_train_step_with_attn_dropout(ndev):
    """The full sp train step with the reference's attention-probability
    dropout enabled (the shipped entrypoint default): runs, converges on
    repeated steps, and differs from the dropout-free trajectory."""
    args = sp_args(attn_dropout=0.1, dropout=0.1)
    batch = make_batch()
    mesh = make_mesh(shape={"data": 2, "seq": 2})
    cfg, tx, state = setup_model(args, V)
    step = make_sp_train_step(cfg, tx, args, mesh)(batch)
    put = make_sp_batch(mesh)
    state1, m1 = step(state, put(batch))
    state2, m2 = step(state1, put(batch))
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))

    cfg0, tx0, state0 = setup_model(args.replace(attn_dropout=0.0), V)
    step0 = make_sp_train_step(cfg0, tx0, args.replace(attn_dropout=0.0), mesh)(batch)
    _, m0 = step0(state0, put(batch))
    assert float(m0["loss"]) != float(m1["loss"])


@pytest.mark.parametrize("mesh_shape", [{"data": 2, "seq": 4},
                                        {"data": 1, "seq": 8}])
def test_sp_train_step_matches_single_device(mesh_shape, ndev):
    if np.prod(list(mesh_shape.values())) > ndev:
        pytest.skip("not enough devices")
    args = sp_args()
    batch = make_batch()

    cfg, tx, state = setup_model(args, V)
    sstate, sm = make_train_step(cfg, tx, args)(state, batch)
    sem = make_eval_step(cfg, args)(sstate["params"], batch)

    mesh = make_mesh(shape=mesh_shape)
    cfg2, tx2, state2 = setup_model(args, V)
    put = make_sp_batch(mesh)
    step = make_sp_train_step(cfg2, tx2, args, mesh)(batch)
    pstate, pm = step(state2, put(batch))
    pem = make_sp_eval_step(cfg2, args, mesh)(batch)(pstate["params"], put(batch))

    assert float(pm["loss"]) == pytest.approx(float(sm["loss"]), rel=1e-5)
    assert float(pem["correct"]) == pytest.approx(float(sem["correct"]), abs=0.5)
    for a, b in zip(jax.tree_util.tree_leaves(sstate["params"]),
                    jax.tree_util.tree_leaves(pstate["params"])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
    # eval echoes the full global label/pred stream
    np.testing.assert_array_equal(np.asarray(pem["label"]), batch["label"])


def test_sp_long_sequence_beyond_single_shard(ndev):
    """The point of the path: a global sequence longer than any single
    shard's local length trains without materializing full-S activations."""
    args = sp_args(max_seq_len=16 * ndev)
    batch = make_batch(n=8, seed=2, seq=16 * ndev, full_mask=True)
    mesh = make_mesh(shape={"data": 1, "seq": ndev})
    cfg, tx, state = setup_model(args, V)
    step = make_sp_train_step(cfg, tx, args, mesh)(batch)
    state, m = step(state, make_sp_batch(mesh)(batch))
    assert np.isfinite(float(m["loss"]))


def test_sp_long_context_config_4x_table(ndev):
    """The long-context configs pair with the ring: bert-tiny-long's 512
    position table carries a global sequence 4x the base bert-tiny limit,
    sharded 64-per-device over the seq axis, and reproduces the
    single-device full-attention run at the same global length."""
    Sg = 512
    args = sp_args(model="bert-tiny-long", max_seq_len=Sg)
    batch = make_batch(n=4, seed=3, seq=Sg, full_mask=True)
    cfg, tx, state = setup_model(args, V)
    sstate, sm = make_train_step(cfg, tx, args)(state, batch)

    mesh = make_mesh(shape={"data": 1, "seq": ndev})
    cfg2, tx2, state2 = setup_model(args, V)
    step = make_sp_train_step(cfg2, tx2, args, mesh)(batch)
    pstate, pm = step(state2, make_sp_batch(mesh)(batch))
    assert float(pm["loss"]) == pytest.approx(float(sm["loss"]), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sstate["params"]),
                    jax.tree_util.tree_leaves(pstate["params"])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
    # the base config loudly refuses the same global length
    short = sp_args(model="bert-tiny", max_seq_len=Sg)
    cfg3, tx3, state3 = setup_model(short, V)
    with pytest.raises(ValueError, match="max_position"):
        make_train_step(cfg3, tx3, short)(state3, batch)
