"""Manifold-constrained hyper-connections (mHC): the residual as ``n``
STREAMS that every sub-layer reads through a learned, input-dependent row,
writes through another and carries through a doubly-stochastic matrix.

Pure functions that ``models/latent_moe._layer`` calls when ``cfg.hc_mult``
> 1 (Xing4.0-29B-A4B: ``hc_mult`` 4, 20 Sinkhorn steps); at 1 none of this
runs and the layer is ``x + F(x)`` as it was.  For ONE sub-layer ``F`` on a
token's streams ``X [n, C]``, all of it float32 whatever the compute dtype
(``benchmark/reference/xing4.py`` states it again, a token at a time):

    x~     = rms_norm(vec(X))                      # [n*C], no weight
    h_pre  = a_pre  * (x~ @ phi_pre)  + b_pre      # [n]
    h_post = a_post * (x~ @ phi_post) + b_post     # [n]
    h_res  = a_res  * mat(x~ @ phi_res) + b_res    # [n, n]
    H_pre  = sigmoid(h_pre);  H_post = 2 * sigmoid(h_post)
    M      = exp(clip(h_res, clamp))
    repeat iters times:  M /= M.sum(-1) + eps;  M /= M.sum(-2) + eps
    u  = H_pre @ X;   y = F(u);   X' = M @ X + outer(H_post, y)

**Layout.**  The streams are ``[n, B, T, C]`` — ``n`` MAJOR, so that a
stream is a slab and no axis of 4 sits on the chip's sublanes (``[B, T, n,
C]`` pads 4 to 16 in bfloat16: four times the bytes).  ``phi_pre``,
``phi_post`` and ``phi_res`` are the column blocks of ONE leaf ``phi [n*C,
n + n + n*n]`` (so the three products are one), ``b`` likewise, ``a`` the
three scalars.  The coefficients live as ``[.., tokens]`` with the tokens
on the minor axis (lanes), so a sum over ``n`` never crosses them.  The 40
normalisations stay a chain of about 80 small dependent fusions a mixing
whichever way they are written (sums as reductions, as adds of slices, as
reciprocal multiplies, over scaling vectors, under a ``fori_loop``: read
from described-v5e compiles, PERF.md section 6): latency, not bandwidth.

The norm is a scalar a token, so ``x~ @ phi = (vec(X) @ phi) * rsqrt(mean
X^2 + eps)`` and the normed streams are never written out.  The streams and
``phi`` are both held in bfloat16, whose products are exact in float32:
``vec(X) @ phi`` accumulated in float32 IS the float32 product.
"""
from __future__ import annotations

import functools
import operator
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
F32 = jnp.float32


def coeff_width(n: int) -> int:
    """Columns of ``phi`` (and entries of ``b``): ``[pre | post | res]``."""
    return n * (n + 2)


def param_shapes(n: int, width: int, lead: Tuple[int, ...] = ()) -> dict:
    """One sub-layer's mixing leaves over ``n`` streams of ``width``."""
    k = coeff_width(n)
    return {"phi": lead + (n * width, k), "b": lead + (k,), "a": lead + (3,)}


def init_leaf(key: jax.Array, name: str, shape: Tuple[int, ...], n: int
              ) -> jax.Array:
    """Seeded float32 values of the mixing leaf ``name`` (``phi`` / ``b`` /
    ``a``): ``phi`` normal / sqrt(n * C), so that ``x~ @ phi`` is of order
    one; ``a`` 1 + 0.1 normal; ``b`` normal, its ``res`` block 2 I + 0.5
    normal — ``H_res`` then leans on the diagonal and is visibly not the
    identity (about 0.6 on it, 0.13 off it)."""
    x = jax.random.normal(key, shape, F32)
    if name == "phi":
        return x * shape[-2] ** -0.5
    if name == "a":
        return 1.0 + 0.1 * x
    eye = jnp.eye(n, dtype=F32).reshape(-1)
    return jnp.concatenate(
        [x[..., :2 * n], 2.0 * eye + 0.5 * x[..., 2 * n:]], axis=-1)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a [B, T, C] @ b [C, k]`` accumulated in and returned as float32:
    exact products for bfloat16 operands (promoted first on the CPU, which
    has no such dot), every pass of the unit for float32 ones."""
    if a.dtype == F32 or jax.default_backend() == "cpu":
        a, b = a.astype(F32), b.astype(F32)
    return jnp.einsum("btc,ck->btk", a, b, preferred_element_type=F32,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("iters", "eps"))
def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """Positive ``m [n, n, ...]`` (row, column) -> doubly stochastic: rows
    normalised, then columns, ``iters`` times.  Jitted, so that a program
    with many sub-layers traces and lowers the unrolled chain once."""
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(0, keepdims=True) + eps)
    return m


def coefficients(x: jax.Array, p: Params, cfg):
    """Streams ``x [n, B, T, C]`` -> float32 (``H_pre [n, B*T]``, ``H_post
    [n, B*T]``, ``H_res [n, n, B*T]``) of one sub-layer's leaves ``p``."""
    n, c = x.shape[0], x.shape[-1]
    lo, hi = cfg.hc_res_clamp
    with jax.named_scope("mhc.coeff"):
        phi = p["phi"].astype(x.dtype).reshape(n, c, -1)
        xf = x.astype(F32)
        ms = jnp.sum(xf * xf, axis=(0, -1)) / (n * c)                 # [B, T]
        h = functools.reduce(operator.add,
                             [_dot(x[i], phi[i]) for i in range(n)])
        h = h * jax.lax.rsqrt(ms + cfg.rms_norm_eps)[..., None]
        h = h.reshape(-1, h.shape[-1]).T                           # [k, B*T]
        a, b = p["a"].astype(F32), p["b"].astype(F32)[:, None]
        pre = jax.nn.sigmoid(a[0] * h[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * h[n:2 * n] + b[n:2 * n])
        res = (a[2] * h[2 * n:] + b[2 * n:]).reshape(n, n, -1)
        res = sinkhorn(jnp.exp(jnp.clip(res, lo, hi)),
                       iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps)
    return pre, post, res


def _per_token(h: jax.Array, x: jax.Array) -> jax.Array:
    """``h [B*T]`` against ``x [B, T, C]``."""
    return h.reshape(x.shape[:-1] + (1,))


def read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """``u = H_pre @ X``: what the sub-layer reads, ``[B, T, C]`` in the
    streams' dtype."""
    with jax.named_scope("mhc.mix"):
        u = functools.reduce(operator.add, [
            _per_token(pre[i], x[i]) * x[i].astype(F32)
            for i in range(x.shape[0])])
    return u.astype(x.dtype)


def write(x: jax.Array, y: jax.Array, res: jax.Array, post: jax.Array
          ) -> jax.Array:
    """``X' = H_res @ X + outer(H_post, y)`` with the sub-layer's output ``y
    [B, T, C]``, summed in float32 -> the streams' dtype."""
    n = x.shape[0]
    with jax.named_scope("mhc.mix"):
        xf, yf = x.astype(F32), y.astype(F32)
        rows = [functools.reduce(operator.add, [
            _per_token(res[i, j], yf) * xf[j] for j in range(n)])
            + _per_token(post[i], yf) * yf for i in range(n)]
    return jnp.stack(rows).astype(x.dtype)
