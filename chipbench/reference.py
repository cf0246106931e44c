"""Plain float32 reference of the served architectures, and its control.

The reference is the published layer equations in ``jax.numpy``: float32
arithmetic at ``highest`` matmul precision, full causal attention over the
whole sequence, no cache, no kernels, no batching.  It imports nothing of the
program and reads only the benchmark's own weights (``weights.make_weights``).
It runs one layer at a time, so that it fits beside the bf16 weights after
the program's state is freed.

``served_gaps`` is the comparison that decides ``correct``: for every served
token, how far its reference logit lies below the reference's best logit at
that position.  ``control_gaps`` is the same number for the reference itself
computed in the precision below the configuration's (every matmul operand
rounded to float8 e4m3 with a per-tensor scale): the limit must fail it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
MIN_BUCKET = 256


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a, b, low: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotary embedding on (S, H, Dh), halves convention (rotate_half)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("d", "low"))
def _layer(x, layers, i, *, d: Dims, low: bool):
    w = jax.tree.map(lambda a: a[i], layers)
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, w["norm1"], d.norm_eps)
    q = _mm("sd,dhk->shk", h, w["wq"], low)
    k = _mm("sd,dhk->shk", h, w["wk"], low)
    v = _mm("sd,dhk->shk", h, w["wv"], low)
    if d.qkv_bias:
        q, k, v = (q + w["bq"].astype(jnp.float32), k + w["bk"].astype(jnp.float32),
                   v + w["bv"].astype(jnp.float32))
    if d.qk_norm:
        q, k = _rms(q, w["q_norm"], d.norm_eps), _rms(k, w["k_norm"], d.norm_eps)
    q, k = _rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta)
    group = d.n_heads // d.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = _mm("qhk,shk->hqs", qb, k, low) / d.head_dim ** 0.5
        mask = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(_mm("hqs,shk->qhk", p, v, low))
    x = x + _mm("shk,hkd->sd", jnp.concatenate(outs, 0), w["wo"], low)
    h = _rms(x, w["norm2"], d.norm_eps)
    g = _mm("sd,df->sf", h, w["w_gate"], low)
    u = _mm("sd,df->sf", h, w["w_up"], low)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], low)


@functools.partial(jax.jit, static_argnames=("d", "low"))
def _head(weights, x, rows, *, d: Dims, low: bool):
    h = _rms(x[rows], weights["final_norm"], d.norm_eps)
    if d.tie_embeddings:
        return _mm("sd,vd->sv", h, weights["embed"], low)
    return _mm("sd,dv->sv", h, weights["head"], low)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def logits(weights: dict, d: Dims, tokens, rows, *, low: bool = False) -> jax.Array:
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``, padded
    on the right to a power of two (causality keeps the rows exact)."""
    toks = np.zeros(bucket(len(tokens)), np.int32)
    toks[: len(tokens)] = tokens
    x = _embed(weights["embed"], jnp.asarray(toks))
    for i in range(d.n_layers):
        x = _layer(x, weights["layers"], jnp.int32(i), d=d, low=low)
    return _head(weights, x, jnp.asarray(rows, jnp.int32), d=d, low=low)


def _rows(prompt, served):
    """The sequence the reference reads and the rows whose logits chose
    each served token: token j was chosen at position len(prompt) - 1 + j."""
    seq = list(prompt) + list(served[:-1])
    return seq, np.arange(len(prompt) - 1, len(seq))


@jax.jit
def _gaps(ref, tokens):
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]


def served_gaps(weights: dict, d: Dims, prompt, served) -> np.ndarray:
    """Reference best logit minus the reference logit of each served token."""
    seq, rows = _rows(prompt, served)
    ref = logits(weights, d, seq, rows)
    return np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32)))


def control_gaps(weights: dict, d: Dims, prompt, served) -> np.ndarray:
    """The same gap for the token the float8 computation puts first, read at
    the same positions of the same sequence."""
    seq, rows = _rows(prompt, served)
    ref = logits(weights, d, seq, rows)
    low = logits(weights, d, seq, rows, low=True)
    return np.asarray(_gaps(ref, jnp.argmax(low, -1).astype(jnp.int32)))
