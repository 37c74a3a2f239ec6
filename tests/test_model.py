"""Model tests (bert-tiny on the 8-device CPU harness's default device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pdnlp_tpu.models import bert, get_config


@pytest.fixture(scope="module")
def cfg():
    return get_config("bert-tiny", vocab_size=100, num_labels=6)


@pytest.fixture(scope="module")
def params(cfg):
    return bert.init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def batch(cfg):
    rng = np.random.RandomState(0)
    B, S = 4, 16
    ids = rng.randint(5, cfg.vocab_size, size=(B, S)).astype(np.int32)
    mask = np.ones((B, S), np.int32)
    mask[1, 10:] = 0  # one padded row
    ids[1, 10:] = 0
    return {
        "input_ids": jnp.asarray(ids),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.asarray(mask),
        "label": jnp.asarray(rng.randint(0, 6, size=(B,)), jnp.int32),
        "example_weight": jnp.ones((B,), jnp.float32),
    }


def test_logits_shape_and_dtype(cfg, params, batch):
    logits = bert.classify(params, cfg, batch)
    assert logits.shape == (4, 6)
    assert logits.dtype == jnp.float32


def test_deterministic_forward(cfg, params, batch):
    a = bert.classify(params, cfg, batch)
    b = bert.classify(params, cfg, batch)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padding_invariance(cfg, params, batch):
    """Tokens behind attention_mask==0 must not change the [CLS] logits."""
    poked = dict(batch)
    ids = np.asarray(batch["input_ids"]).copy()
    ids[1, 10:] = 7  # rewrite masked positions
    poked["input_ids"] = jnp.asarray(ids)
    a = bert.classify(params, cfg, batch)
    b = bert.classify(params, cfg, poked)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_dropout_stochastic_but_seeded(cfg, params, batch):
    k = jax.random.key(42)
    a = bert.classify(params, cfg, batch, deterministic=False, rng=k)
    b = bert.classify(params, cfg, batch, deterministic=False, rng=k)
    c = bert.classify(params, cfg, batch, deterministic=False, rng=jax.random.key(43))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_bf16_close_to_f32(cfg, params, batch):
    a = bert.classify(params, cfg, batch)
    b = bert.classify(params, cfg, batch, dtype=jnp.bfloat16)
    assert b.dtype == jnp.float32  # logits promoted back
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0.1, atol=0.15)


def test_remat_matches(cfg, params, batch):
    a = bert.classify(params, cfg, batch)
    b = bert.classify(params, cfg, batch, remat=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


def test_grads_finite(cfg, params, batch):
    def loss_fn(p):
        logits = bert.classify(p, cfg, batch)
        onehot = jax.nn.one_hot(batch["label"], 6)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    grads = jax.grad(loss_fn)(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves and all(bool(jnp.isfinite(g).all()) for g in leaves)
    # every parameter receives gradient somewhere
    nonzero = sum(float(jnp.abs(g).sum()) > 0 for g in leaves)
    assert nonzero >= len(leaves) - 1  # token_type may be degenerate w/ all-zero types


def test_param_count_bert_base_matches_reference_scale():
    """BERT-base @ vocab 21128 must land at the reference's ~102M params."""
    cfg = get_config("bert-base")
    n = 0
    H, L, I = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size
    n += cfg.vocab_size * H + cfg.max_position * H + cfg.type_vocab_size * H + 2 * H
    n += L * (4 * (H * H + H) + 2 * H + H * I + I + I * H + H + 2 * H)
    n += H * H + H + H * cfg.num_labels + cfg.num_labels
    assert 100e6 < n < 105e6
    tiny = get_config("bert-tiny", vocab_size=100)
    p = bert.init_params(jax.random.key(0), tiny)
    assert bert.param_count(p) > 0


def test_gelu_config_knob(cfg, params, batch):
    """``cfg.gelu`` selects the activation: the registry default is exact
    erf (the reference model); "tanh" changes the forward by at most the
    approximation error, and an Args-level ``--gelu`` override reaches the
    config (``models/config.py:args_overrides``)."""
    from pdnlp_tpu.models.config import args_overrides
    from pdnlp_tpu.utils.config import Args

    assert cfg.gelu == "erf"
    a = bert.classify(params, cfg, batch)
    b = bert.classify(params, cfg.replace(gelu="tanh"), batch)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)

    assert "gelu" not in args_overrides(Args())  # None keeps the default
    assert args_overrides(Args(gelu="tanh"))["gelu"] == "tanh"
    assert get_config("bert-base", **args_overrides(Args(gelu="tanh"))).gelu == "tanh"

    # a typo'd value must fail loudly, not silently run erf (a pretrain
    # cache is keyed on the raw string)
    with pytest.raises(ValueError, match="gelu"):
        bert.classify(params, cfg.replace(gelu="Tanh"), batch)
