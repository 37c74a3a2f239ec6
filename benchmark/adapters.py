"""Where the benchmark's names meet the program's pytrees.

The benchmark's weights (``reference/weights.py``) are laid into the
program's parameter tree here, and the program's optimizer moments and
parameters are read back under the reference's names.  These layouts are
touch points (see ``README.md``): a program that changes its tree has to
keep an entry point that takes this one.
"""
from __future__ import annotations

LAYERS = ("q", "k", "v", "o", "up", "down")


def _dense(w, n):
    return {"kernel": w[f"{n}_w"], "bias": w[f"{n}_b"]}


def _ln(w, n):
    return {"scale": w[f"{n}_g"], "bias": w[f"{n}_b"]}


def to_program_params(w: dict) -> dict:
    layers = {n: _dense(w, n) for n in LAYERS}
    layers["attn_ln"] = _ln(w, "attn_ln")
    layers["mlp_ln"] = _ln(w, "mlp_ln")
    return {
        "embeddings": {"word": w["word"], "position": w["pos"],
                       "token_type": w["type"], "ln": _ln(w, "emb_ln")},
        "layers": layers,
        "pooler": _dense(w, "pooler"),
        "classifier": _dense(w, "cls"),
    }


def to_program_head(w: dict) -> dict:
    return {"transform": _dense(w, "head"), "ln": _ln(w, "head_ln"),
            "bias": w["out_b"]}


def from_program_params(tree: dict) -> dict:
    """The inverse of ``to_program_params`` (optimizer moments share the
    parameters' tree)."""
    out = {"word": tree["embeddings"]["word"],
           "pos": tree["embeddings"]["position"],
           "type": tree["embeddings"]["token_type"],
           "emb_ln_g": tree["embeddings"]["ln"]["scale"],
           "emb_ln_b": tree["embeddings"]["ln"]["bias"],
           "pooler_w": tree["pooler"]["kernel"],
           "pooler_b": tree["pooler"]["bias"],
           "cls_w": tree["classifier"]["kernel"],
           "cls_b": tree["classifier"]["bias"]}
    for n in LAYERS:
        out[f"{n}_w"] = tree["layers"][n]["kernel"]
        out[f"{n}_b"] = tree["layers"][n]["bias"]
    for n in ("attn_ln", "mlp_ln"):
        out[f"{n}_g"] = tree["layers"][n]["scale"]
        out[f"{n}_b"] = tree["layers"][n]["bias"]
    return out


def check_same_tree(mine, theirs, what: str) -> None:
    """Refuse to go on when the program's tree is not the one laid out here."""
    import jax

    a = jax.tree_util.tree_structure(mine)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise SystemExit(
            f"benchmark: the program's {what} tree is not the one "
            f"benchmark/adapters.py lays out\n  benchmark: {a}\n  program:   {b}")
    for x, y in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        if tuple(x.shape) != tuple(y.shape):
            raise SystemExit(
                f"benchmark: a leaf of the program's {what} tree has shape "
                f"{tuple(y.shape)}, the configuration gives {tuple(x.shape)}")
