"""GQA attention: full-sequence (train), prefill (returns cache), decode.

Memory discipline: full (Sq, Skv) logits are only materialized when
``S <= cfg.attn_chunk``; beyond that the jnp chunked-flash path (lax.scan over
query chunks with online softmax over key chunks) keeps the live logits block
at ``attn_chunk^2``.  On TPU backends the Pallas flash kernel takes over via
``kernels/flash_attention``.

Decode reads a cache laid out (B, S, Hkv, Dh) so the sequence dim can shard
over the `model` mesh axis: the softmax max/sum and the S-contraction then
lower to all-reduces over `model`, which keeps decode TP head-count agnostic
(granite has 1 KV head; qwen2-1.5b has 12 Q heads — neither divides 16).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels.flash_attention.ops import mha as flash_mha
from ..kernels.prefill.ops import prefill_attention
from .config import ModelConfig
from .layers import apply_rope, cache_write, dense_init, dtype_of, pos_column, rms_norm

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class KVCache:
    k: jax.Array       # (B, S, Hkv, Dh)
    v: jax.Array       # (B, S, Hkv, Dh)


jax.tree_util.register_dataclass(KVCache, data_fields=["k", "v"], meta_fields=[])


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    dt = dtype_of(cfg.param_dtype)
    hq, hkv, dh = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, hq, dh), dt),
        "wk": dense_init(ks[1], (cfg.d_model, hkv, dh), dt),
        "wv": dense_init(ks[2], (cfg.d_model, hkv, dh), dt),
        "wo": dense_init(ks[3], (hq, dh, cfg.d_model), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, dh), dt)
        p["bk"] = jnp.zeros((hkv, dh), dt)
        p["bv"] = jnp.zeros((hkv, dh), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    del cross
    return p


def _project_qkv(p: dict, cfg: ModelConfig, xq: jax.Array, xkv: jax.Array,
                 q_positions, kv_positions, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, q_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, kv_positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def _sdpa_full(q, k, v, *, causal: bool, kv_mask=None) -> jax.Array:
    """Materialized-logits attention, f32 softmax.  q:(B,S,H,D) k/v:(B,T,Hkv,D).

    GQA is handled by *grouped einsum* — Q is reshaped to (B,S,Hkv,G,D) so
    K/V are never jnp.repeat-materialized (saves (G-1)x KV bytes, which at
    decode time means not rewriting the whole cache G times)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) / dh ** 0.5
    if causal:
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((qi >= kj)[None, None, None], s, NEG_INF)
    if kv_mask is not None:  # (B, T) valid-key mask
        s = jnp.where(kv_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(b, sq, hq, dh)


def _sdpa_chunked(q, k, v, *, causal: bool, chunk: int = 1024,
                  chunk_k: int = 0, unroll: bool = False) -> jax.Array:
    """jnp flash: scan over query chunks, online softmax over key chunks.
    GQA via grouped einsum (no KV repeat).  ``chunk_k`` may differ from the
    q-chunk: online-softmax carry traffic scales with S*cq/ck while the score
    blocks are chunk-size invariant, so small-q/large-k cuts carry bytes."""
    b, sq, hq, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    cq = min(chunk, sq)
    ck = min(chunk_k or chunk, t)
    pad_q = (-sq) % cq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nq = q.shape[1] // cq
    pad_k = (-t) % ck
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nk = k.shape[1] // ck
    kb = k.reshape(b, nk, ck, hkv, dh)
    vb = v.reshape(b, nk, ck, hkv, dh)
    kv_valid = (jnp.arange(nk * ck) < t).reshape(nk, ck)

    def q_chunk(carry, iq):
        qc = jax.lax.dynamic_slice_in_dim(q, iq * cq, cq, axis=1)  # (B,cq,H,D)
        qf = (qc.astype(jnp.float32) / dh ** 0.5).reshape(b, cq, hkv, g, dh)

        def kv_step(state, ik):
            m, l, acc = state
            kc = kb[:, ik].astype(jnp.float32)
            vc = vb[:, ik].astype(jnp.float32)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc)
            mask = kv_valid[ik][None, None, None, None, :]
            if causal:
                qi = iq * cq + jnp.arange(cq)[:, None]
                kj = ik * ck + jnp.arange(ck)[None, :]
                mask = mask & (qi >= kj)[None, None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            pblk = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)                     # (B,Hkv,G,cq,1)
            l_new = l * alpha + jnp.sum(pblk, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgqk,bkhd->bhgqd", pblk, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, cq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk), unroll=True if unroll else 1
        )
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)   # (B,Hkv,G,cq,D)
        return carry, out.transpose(0, 3, 1, 2, 4).reshape(b, cq, hq, dh)

    _, outs = jax.lax.scan(
        q_chunk, 0, jnp.arange(nq), unroll=True if unroll else 1
    )                                                         # (nq,B,cq,H,D)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * cq, hq, dh)
    return out[:, :sq]


def _use_pallas(cfg: ModelConfig) -> bool:
    if cfg.use_pallas is None:
        return jax.default_backend() == "tpu"
    return cfg.use_pallas


def attention_train(
    p: dict, cfg: ModelConfig, x: jax.Array, positions, *,
    causal: bool = True, xkv: jax.Array | None = None, kv_positions=None,
    rope: bool = True,
) -> jax.Array:
    """Full-sequence attention (training / encoder / cross)."""
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, cfg, x, xkv, positions, kv_positions, rope=rope)
    if _use_pallas(cfg):
        out = flash_mha(q, k, v, causal=causal,
                        use_pallas=True, interpret=jax.default_backend() != "tpu")
    elif q.shape[1] * k.shape[1] <= cfg.attn_chunk ** 2:
        out = _sdpa_full(q, k, v, causal=causal)
    else:
        out = _sdpa_chunked(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                            chunk_k=cfg.attn_chunk_k, unroll=cfg.full_unroll)
    return jnp.einsum("bshd,hdm->bsm", out, p["wo"])


def attention_prefill(
    p: dict, cfg: ModelConfig, x: jax.Array, positions,
) -> tuple[jax.Array, KVCache]:
    """Causal attention over the prompt; returns output + KV cache (pre-rope
    keys are *not* cached — rope is applied before caching, standard).

    The Pallas branch uses the fused bucketed-prefill op, which also
    materializes the cache tensors in the storage dtype in-kernel (the KV
    handoff payload for disaggregated serving)."""
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions)
    if _use_pallas(cfg):
        out, kc, vc = prefill_attention(
            q, k, v, cache_dtype=dtype_of(cfg.cache_dtype or cfg.compute_dtype),
            use_pallas=True, interpret=jax.default_backend() != "tpu")
        return jnp.einsum("bshd,hdm->bsm", out, p["wo"]), KVCache(k=kc, v=vc)
    if x.shape[1] <= cfg.attn_chunk:
        out = _sdpa_full(q, k, v, causal=True)
    else:
        out = _sdpa_chunked(q, k, v, causal=True, chunk=cfg.attn_chunk,
                            chunk_k=cfg.attn_chunk_k, unroll=cfg.full_unroll)
    return jnp.einsum("bshd,hdm->bsm", out, p["wo"]), KVCache(k=k, v=v)


def init_kv_cache(cfg: ModelConfig, batch: int, seq: int) -> KVCache:
    dt = dtype_of(cfg.cache_dtype or cfg.compute_dtype)
    shape = (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))


def attention_decode(
    p: dict, cfg: ModelConfig, x: jax.Array, cache: KVCache, pos: jax.Array,
    *, cross: bool = False, cross_len: jax.Array | None = None, layer=None,
) -> tuple[jax.Array, KVCache]:
    """One-token decode.  x: (B,1,d).  pos: scalar or per-slot (B,) index.

    Self-attn: writes K/V at `pos`, attends over cache[<= pos].  Given
    `layer`, `cache` is the whole stack (L,B,S,Hkv,Dh): the row goes into
    layer `layer` of it, that layer is read, and the stack is returned.
    Cross-attn (enc-dec): cache holds the encoder memory; no write.
    """
    b = x.shape[0]
    if cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k, v = cache.k, cache.v
        valid = jnp.arange(k.shape[1]) < (
            cross_len if cross_len is not None else k.shape[1]
        )
    else:
        pos_b = pos_column(pos, b)
        q, k_t, v_t = _project_qkv(p, cfg, x, x, pos_b, pos_b)
        cache = KVCache(
            k=cache_write(cache.k, k_t, pos, cfg.cache_update, layer),
            v=cache_write(cache.v, v_t, pos, cfg.cache_update, layer),
        )
        k, v = cache.k, cache.v
        if layer is not None:
            k, v = k[layer], v[layer]
        valid = jnp.arange(k.shape[1])[None, :] <= pos_b
    kv_mask = jnp.broadcast_to(valid, (b, k.shape[1]))
    out = _sdpa_full(
        q, k.astype(x.dtype), v.astype(x.dtype), causal=False, kv_mask=kv_mask
    )
    return jnp.einsum("bshd,hdm->bsm", out, p["wo"]), cache
