"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Queries go through a low-rank bottleneck (q_lora); keys/values share a
compressed latent c_kv (kv_lora=512) plus a single shared rope key stream
(qk_rope=64).  The decode cache stores only (c_kv, k_rope) per token —
(512+64) values/layer instead of 2*H*Dh — which is the paper's point.

Decode runs in the *absorbed* form: W_UK folds into the query and W_UV into
the output so attention happens directly in latent space; nothing of size
(S, H, Dh) is ever materialized against the 32k cache.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, cache_write, dense_init, dtype_of, pos_column, rms_norm

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class MLACache:
    c_kv: jax.Array     # (B, S, kv_lora)
    k_rope: jax.Array   # (B, S, rope_dim)


jax.tree_util.register_dataclass(MLACache, data_fields=["c_kv", "k_rope"], meta_fields=[])


def init_mla(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    dt = dtype_of(cfg.param_dtype)
    h = cfg.n_q_heads
    ks = jax.random.split(key, 8)
    return {
        "wdq": dense_init(ks[0], (cfg.d_model, m.q_lora_rank), dt),
        "q_norm": jnp.ones((m.q_lora_rank,), dt),
        "wuq": dense_init(
            ks[1], (m.q_lora_rank, h, m.qk_nope_head_dim + m.qk_rope_head_dim), dt
        ),
        "wdkv": dense_init(ks[2], (cfg.d_model, m.kv_lora_rank), dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "wkr": dense_init(ks[3], (cfg.d_model, m.qk_rope_head_dim), dt),
        "wuk": dense_init(ks[4], (m.kv_lora_rank, h, m.qk_nope_head_dim), dt),
        "wuv": dense_init(ks[5], (m.kv_lora_rank, h, m.v_head_dim), dt),
        "wo": dense_init(ks[6], (h, m.v_head_dim, cfg.d_model), dt),
    }


def _latents(p: dict, cfg: ModelConfig, x: jax.Array, positions):
    """Shared front end: q (rope'd), compressed kv latent, rope'd shared key."""
    m = cfg.mla
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdq"]), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta)
    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(
        jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_train(
    p: dict, cfg: ModelConfig, x: jax.Array, positions, *, causal: bool = True
) -> jax.Array:
    """Naive (decompressed) form for train/prefill — chunked over queries."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _latents(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"])
    scale = 1.0 / (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    b, s, h, _ = q_nope.shape
    cq = min(cfg.attn_chunk, s)
    pad = (-s) % cq
    qn = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q_nope
    qr = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q_rope
    nq = (s + pad) // cq

    def q_chunk(_, iq):
        qnc = jax.lax.dynamic_slice_in_dim(qn, iq * cq, cq, axis=1)
        qrc = jax.lax.dynamic_slice_in_dim(qr, iq * cq, cq, axis=1)
        sc = (
            jnp.einsum("bqhk,bshk->bhqs", qnc, k_nope)
            + jnp.einsum("bqhk,bsk->bhqs", qrc, k_rope)
        ).astype(jnp.float32) * scale
        if causal:
            qi = iq * cq + jnp.arange(cq)[:, None]
            kj = jnp.arange(s)[None, :]
            sc = jnp.where((qi >= kj)[None, None], sc, NEG_INF)
        attn = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqs,bshk->bqhk", attn, v)
        return _, out

    _, outs = jax.lax.scan(
        q_chunk, 0, jnp.arange(nq), unroll=True if cfg.full_unroll else 1
    )                                                    # (nq,B,cq,H,Dv)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * cq, h, m.v_head_dim)[:, :s]
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_prefill(
    p: dict, cfg: ModelConfig, x: jax.Array, positions
) -> tuple[jax.Array, MLACache]:
    out = mla_train(p, cfg, x, positions, causal=True)
    m = cfg.mla
    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(
        jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    del m
    return out, MLACache(c_kv=c_kv, k_rope=k_rope)


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int) -> MLACache:
    m = cfg.mla
    dt = dtype_of(cfg.cache_dtype or cfg.compute_dtype)
    return MLACache(
        c_kv=jnp.zeros((batch, seq, m.kv_lora_rank), dt),
        k_rope=jnp.zeros((batch, seq, m.qk_rope_head_dim), dt),
    )


def mla_decode(
    p: dict, cfg: ModelConfig, x: jax.Array, cache: MLACache, pos, layer=None
) -> tuple[jax.Array, MLACache]:
    """Absorbed-form decode: attention entirely in the 512-d latent space.
    Given `layer`, `cache` is the whole stack, as in `attention_decode`."""
    m = cfg.mla
    b = x.shape[0]
    pos_b = pos_column(pos, b)
    q_nope, q_rope, c_kv_t, k_rope_t = _latents(p, cfg, x, pos_b)
    cache = MLACache(
        c_kv=cache_write(cache.c_kv, c_kv_t, pos, cfg.cache_update, layer),
        k_rope=cache_write(cache.k_rope, k_rope_t, pos, cfg.cache_update, layer),
    )
    c_kv, k_rope = cache.c_kv, cache.k_rope
    if layer is not None:
        c_kv, k_rope = c_kv[layer], k_rope[layer]
    # Absorb W_UK into the query: q_lat (B,1,H,kv_lora).
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wuk"])
    scale = 1.0 / (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    ckv = c_kv.astype(x.dtype)
    krp = k_rope.astype(x.dtype)
    logits = (
        jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv)
        + jnp.einsum("bqhk,bsk->bhqs", q_rope, krp)
    ).astype(jnp.float32) * scale
    valid = jnp.arange(c_kv.shape[1])[None, :] <= pos_b   # (B, S)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", attn, ckv)          # (B,1,H,kv_lora)
    out = jnp.einsum("bqhr,rhk->bqhk", ctx_lat, p["wuv"])      # absorb W_UV
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"]), cache
