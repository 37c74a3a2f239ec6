"""Seeded weights, made by the benchmark and by nothing else.

One jitted call makes every leaf on the device from ``--seed``.  The program
under test is GIVEN these weights (``benchmark/adapters.py`` lays them into
its pytree) and the plain reference below uses the same call, so neither side
reads what the other made.  Names are the reference's own; ``y = x @ w + b``.

Values: kernels and embeddings normal * 0.02 (BERT's ``initializer_range``),
biases normal * 0.02, LayerNorm gains 1 + normal * 0.1 so that no term of the
mathematics is an identity a faulty path could skip unnoticed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02


def shapes(sizes: dict) -> dict:
    """name -> (shape, kind) for every leaf; ``sizes`` holds the published
    config's numbers (``benchmark/configs/*.json``)."""
    V, H, L = sizes["vocab_size"], sizes["hidden_size"], sizes["num_hidden_layers"]
    I, P, T = (sizes["intermediate_size"], sizes["max_position_embeddings"],
               sizes["type_vocab_size"])
    C = sizes.get("num_labels", 2)
    out = {
        "word": ((V, H), "w"), "pos": ((P, H), "w"), "type": ((T, H), "w"),
        "emb_ln_g": ((H,), "g"), "emb_ln_b": ((H,), "b"),
        "pooler_w": ((H, H), "w"), "pooler_b": ((H,), "b"),
        "cls_w": ((H, C), "w"), "cls_b": ((C,), "b"),
        "head_w": ((H, H), "w"), "head_b": ((H,), "b"),
        "head_ln_g": ((H,), "g"), "head_ln_b": ((H,), "b"),
        "out_b": ((V,), "b"),
    }
    for n, (i, o) in {"q": (H, H), "k": (H, H), "v": (H, H), "o": (H, H),
                      "up": (H, I), "down": (I, H)}.items():
        out[f"{n}_w"] = ((L, i, o), "w")
        out[f"{n}_b"] = ((L, o), "b")
    for n in ("attn_ln", "mlp_ln"):
        out[f"{n}_g"] = ((L, H), "g")
        out[f"{n}_b"] = ((L, H), "b")
    return out


def _leaf(key, shape, kind):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "g":
        return 1.0 + 0.1 * x
    return STD * x


def seed_key(seed: int):
    return jax.random.key(int(seed) % (2 ** 32))


def generate(key, sizes: dict, banned: tuple = ()) -> dict:
    """Every leaf from ``key`` (traceable: call it inside a jitted function).

    ``banned``: ids the served model must never emit (the batcher's EOS) —
    their output bias is -1e4, so a stream runs exactly as long as the
    traffic drew it."""
    table = sorted(shapes(sizes).items())
    keys = jax.random.split(key, len(table))
    out = {n: _leaf(k, s, kind) for k, (n, (s, kind)) in zip(keys, table)}
    if banned:
        out["out_b"] = out["out_b"].at[
            jnp.asarray([int(b) for b in banned])].set(-1e4)
    return out


def make_weights(seed: int, sizes: dict, banned: tuple = (), layout=None,
                 out_shardings=None):
    """Every leaf, float32, made on the device in ONE jitted call.
    ``layout`` maps the flat dict to the tree the caller wants
    (``benchmark/adapters.py``), inside the call, and ``out_shardings``
    places that tree."""
    def make(key):
        out = generate(key, sizes, banned)
        return layout(out) if layout is not None else out

    fn = jax.jit(make) if out_shardings is None else jax.jit(
        make, out_shardings=out_shardings)
    return fn(seed_key(seed))
