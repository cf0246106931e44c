"""Shared neural building blocks (pure jnp; dtype-disciplined).

Conventions:
  - params are plain dict pytrees; leaf names are stable because the sharding
    policy keys on them,
  - compute happens in ``cfg.compute_dtype``; norms/softmax accumulate f32,
  - every initializer takes an explicit PRNG key (init is eval_shape-able).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig


def dtype_of(name: str):
    return jnp.dtype(name)


# ------------------------------------------------------------------ initializers
def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = (scale if scale is not None else 1.0) / max(fan_in, 1) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ------------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def init_norm(cfg: ModelConfig, dim: int | None = None) -> dict:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), dtype_of(cfg.param_dtype))}
    if cfg.use_layernorm:
        p["bias"] = jnp.zeros((d,), dtype_of(cfg.param_dtype))
    return p


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.use_layernorm:
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def gated_rms_norm(x: jax.Array, gate: jax.Array, weight: jax.Array, eps: float):
    """Mamba-2 output norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype), weight, eps)


# ------------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(
    x: jax.Array,            # (B, S, H, D)
    positions: jax.Array,    # (B, S) int or (B, 3, S) for M-RoPE
    theta: float,
    mrope_sections: tuple[int, int, int] | None = None,
) -> jax.Array:
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    if mrope_sections is None:
        if positions.ndim == 3:
            positions = positions[:, 0]
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    else:
        # M-RoPE (Qwen2-VL): frequency bands split across (t, h, w) position
        # streams: first `sections[0]` frequency pairs use the temporal id, etc.
        if positions.ndim == 2:
            positions = jnp.broadcast_to(
                positions[:, None, :], (positions.shape[0], 3, positions.shape[1])
            )
        sec = mrope_sections
        assert sum(sec) == d // 2, (sec, d)
        comp = jnp.concatenate(
            [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sec)]
        )                                               # (D/2,) -> which stream
        pos_sel = positions.astype(jnp.float32)[:, comp, :]   # (B, D/2, S)
        angles = pos_sel.transpose(0, 2, 1) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]               # (B,S,1,D/2)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ----------------------------------------------------------------------- MLP(s)
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    dt = dtype_of(cfg.param_dtype)
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (cfg.d_model, ff), dt),
        "w_up": dense_init(k2, (cfg.d_model, ff), dt),
        "w_down": dense_init(k3, (ff, cfg.d_model), dt),
    }


def apply_mlp(p: dict, x: jax.Array) -> jax.Array:
    """SwiGLU (all assigned LM archs use gated SiLU MLPs)."""
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ------------------------------------------------------------------- embeddings
def init_embedding(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p = {"table": embed_init(k1, (cfg.padded_vocab, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, (cfg.d_model, cfg.padded_vocab), dt)
    return p


def embed_tokens(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return p["table"][tokens]


def lm_logits(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    table = p["head"] if "head" in p else p["table"].T
    logits = jnp.einsum("bsd,dv->bsv", x, table).astype(dtype_of(cfg.logit_dtype))
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        mask = jnp.concatenate(
            [jnp.zeros((cfg.vocab_size,)), jnp.full((pad,), -1e30)]
        ).astype(logits.dtype)
        logits = logits + mask
    return logits


# ----------------------------------------------------------------- decode cache
def pos_column(pos, b: int) -> jax.Array:
    """A decode position, scalar or per-slot ``(B,)``, as an int ``(B, 1)``."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jnp.broadcast_to(jnp.reshape(pos, (1, 1)), (b, 1))
    return pos[:, None]


def cache_write(cache: jax.Array, new: jax.Array, pos, mode: str,
                layer=None) -> jax.Array:
    """Write ``new``, ``(B, 1, ...)``, at sequence index ``pos`` (scalar or
    per-slot ``(B,)``) of a cache: one layer's ``(B, S, ...)`` or, given
    ``layer``, a stack's ``(L, B, S, ...)`` at that layer.  Returns the whole
    cache.

    The write scatters one row per slot, in place where the cache is donated
    or carried; a pos outside ``[0, S)`` writes nothing.  A scatter, not a
    dynamic_update_slice, also for a scalar pos: where the sequence axis is
    sharded, the partitioner turns a dynamic_update_slice into a select over
    the whole local shard, which for a stack is every layer's cache.  With
    ``mode == "onehot"`` a scalar pos selects over the layer's sequence
    axis instead."""
    new = new.astype(cache.dtype)
    pos = jnp.asarray(pos)
    if mode == "onehot" and pos.ndim == 0:
        one = cache if layer is None else cache[layer]
        hit = jnp.arange(one.shape[1]) == pos
        one = jnp.where(hit.reshape((1, -1) + (1,) * (one.ndim - 2)), new, one)
        if layer is None:
            return one
        return jax.lax.dynamic_update_index_in_dim(cache, one, layer, 0)
    rows = jnp.arange(new.shape[0]) if pos.ndim else slice(None)
    idx = (rows, pos) if layer is None else (layer, rows, pos)
    return cache.at[idx].set(new[:, 0], unique_indices=True, mode="drop",
                             wrap_negative_indices=False)
