"""Pattern-period layer stacks: init + apply with lax.scan and remat.

The stack is ``prefix_pattern`` (unrolled layers, e.g. deepseek's first dense
layer) followed by ``n_periods`` repetitions of ``layer_pattern`` executed
under ``lax.scan`` — compile time is O(pattern), not O(depth) (granite has 88
layers; deepseek 60).  Stacked period params/caches carry a leading
``n_periods`` axis on every leaf.

Modes: "train" (no cache), "prefill" (returns caches), "decode" (consumes and
returns caches, one token).  Decode carries the stacked period caches through
the scan and writes each layer's new row into them in place, so a step moves
one row per slot and layer, not the whole cache.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .attention import (
    KVCache,
    attention_decode,
    attention_prefill,
    attention_train,
    init_attention,
    init_kv_cache,
)
from .config import LayerSpec, ModelConfig
from .layers import apply_mlp, apply_norm, init_mlp, init_norm
from .mamba import init_mamba, init_mamba_cache, mamba_decode, mamba_train
from .mla import init_mla, init_mla_cache, mla_decode, mla_prefill, mla_train
from .moe import apply_moe, apply_moe_dense, init_moe


# --------------------------------------------------------------------- layer init
def init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> dict:
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": init_norm(cfg)}
    if spec.mixer == "attn":
        p["attn"] = init_attention(ks[0], cfg)
    elif spec.mixer == "mla":
        p["mla"] = init_mla(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = init_mamba(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        p["norm_cross"] = init_norm(cfg)
        p["cross"] = init_attention(ks[1], cfg)
    if spec.mlp == "dense":
        p["norm2"] = init_norm(cfg)
        p["mlp"] = init_mlp(ks[2], cfg)
    elif spec.mlp == "moe":
        p["norm2"] = init_norm(cfg)
        p["moe"] = init_moe(ks[2], cfg)
    return p


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, seq: int,
                     cross_seq: int | None = None) -> dict:
    c: dict[str, Any] = {}
    if spec.mixer == "attn":
        c["self"] = init_kv_cache(cfg, batch, seq)
    elif spec.mixer == "mla":
        c["self"] = init_mla_cache(cfg, batch, seq)
    elif spec.mixer == "mamba":
        c["self"] = init_mamba_cache(cfg, batch)
    if spec.cross_attn:
        c["cross"] = init_kv_cache(cfg, batch, cross_seq or seq)
    return c


def cross_kv(p_cross: dict, cfg: ModelConfig, memory: jax.Array) -> KVCache:
    """Project encoder memory to K/V once (cached for the whole decode)."""
    k = jnp.einsum("bsd,dhk->bshk", memory, p_cross["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p_cross["wv"])
    if "bk" in p_cross:
        k, v = k + p_cross["bk"], v + p_cross["bv"]
    if "k_norm" in p_cross:
        from .layers import rms_norm

        k = rms_norm(k, p_cross["k_norm"], cfg.norm_eps)
    return KVCache(k=k, v=v)


# -------------------------------------------------------------------- layer apply
def _layer_of(tree, layer):
    """Entry ``layer`` of every stacked leaf (the tree itself when None)."""
    if layer is None:
        return tree
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), tree)


def _put_layer(stack, one, layer):
    """``stack`` with entry ``layer`` replaced by ``one`` (``one`` when None)."""
    if layer is None:
        return one
    return jax.tree.map(
        lambda s, o: jax.lax.dynamic_update_index_in_dim(s, o, layer, 0),
        stack, one)


def apply_layer(
    p: dict, cfg: ModelConfig, spec: LayerSpec, x: jax.Array, *,
    mode: str, positions=None, cache: dict | None = None, pos=None,
    causal: bool = True, cross_memory: jax.Array | None = None,
    mem_positions=None, capacities=None, layer=None,
):
    """Returns (x, new_cache | None, aux_loss scalar).

    Decode given ``layer``: ``cache`` holds the whole period stack, this
    layer is entry ``layer`` of it, and the stack is returned updated."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    h = apply_norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        if mode == "train":
            a = attention_train(p["attn"], cfg, h, positions, causal=causal)
        elif mode == "prefill":
            a, c = attention_prefill(p["attn"], cfg, h, positions)
            new_cache["self"] = c
        else:
            a, c = attention_decode(p["attn"], cfg, h, cache["self"], pos,
                                    layer=layer)
            new_cache["self"] = c
    elif spec.mixer == "mla":
        if mode == "train":
            a = mla_train(p["mla"], cfg, h, positions, causal=causal)
        elif mode == "prefill":
            a, c = mla_prefill(p["mla"], cfg, h, positions)
            new_cache["self"] = c
        else:
            a, c = mla_decode(p["mla"], cfg, h, cache["self"], pos, layer=layer)
            new_cache["self"] = c
    elif spec.mixer == "mamba":
        if mode in ("train", "prefill"):
            a, c = mamba_train(p["mamba"], cfg, h)
            if mode == "prefill":
                new_cache["self"] = c
        else:
            a, c = mamba_decode(p["mamba"], cfg, h,
                                _layer_of(cache["self"], layer))
            new_cache["self"] = _put_layer(cache["self"], c, layer)
    else:
        raise ValueError(spec.mixer)
    x = x + a

    if spec.cross_attn:
        h = apply_norm(cfg, p["norm_cross"], x)
        if mode == "train":
            a = attention_train(
                p["cross"], cfg, h, positions, causal=False,
                xkv=cross_memory, kv_positions=mem_positions, rope=False,
            )
        elif mode == "prefill":
            ckv = cross_kv(p["cross"], cfg, cross_memory)
            new_cache["cross"] = ckv
            a, _ = attention_decode(
                p["cross"], cfg, h, ckv, None, cross=True
            )
        else:
            a, _ = attention_decode(
                p["cross"], cfg, h, _layer_of(cache["cross"], layer), None,
                cross=True,
            )
            new_cache["cross"] = cache["cross"]
        x = x + a

    if spec.mlp != "none":
        h = apply_norm(cfg, p["norm2"], x)
        if spec.mlp == "dense":
            x = x + apply_mlp(p["mlp"], h)
        elif mode == "decode":
            mo, _ = apply_moe_dense(p["moe"], cfg, h)
            x = x + mo
        else:
            mo, moe_aux = apply_moe(p["moe"], cfg, h, capacities)
            x = x + mo
            aux = aux + moe_aux
    return x, (new_cache if mode != "train" else None), aux


# -------------------------------------------------------------------- stack
def init_stack(
    key, cfg: ModelConfig, pattern: tuple[LayerSpec, ...] | None = None,
    prefix: tuple[LayerSpec, ...] | None = None, n_periods: int | None = None,
) -> dict:
    pattern = pattern if pattern is not None else cfg.layer_pattern
    prefix = prefix if prefix is not None else cfg.prefix_pattern
    n_periods = n_periods if n_periods is not None else cfg.n_periods
    kp, ks = jax.random.split(key)
    out: dict[str, Any] = {}
    if prefix:
        out["prefix"] = [
            init_layer(k, cfg, spec)
            for k, spec in zip(jax.random.split(kp, len(prefix)), prefix, strict=True)
        ]
    period_params = {}
    pos_keys = jax.random.split(ks, len(pattern))
    for i, spec in enumerate(pattern):
        keys = jax.random.split(pos_keys[i], n_periods)
        period_params[f"pos{i}"] = jax.vmap(
            lambda k, s=spec: init_layer(k, cfg, s)
        )(keys)
    out["periods"] = period_params
    return out


def init_stack_cache(
    cfg: ModelConfig, batch: int, seq: int, *,
    pattern=None, prefix=None, n_periods=None, cross_seq=None,
) -> dict:
    pattern = pattern if pattern is not None else cfg.layer_pattern
    prefix = prefix if prefix is not None else cfg.prefix_pattern
    n_periods = n_periods if n_periods is not None else cfg.n_periods
    out: dict[str, Any] = {}
    if prefix:
        out["prefix"] = [
            init_layer_cache(cfg, spec, batch, seq, cross_seq) for spec in prefix
        ]
    periods = {}
    for i, spec in enumerate(pattern):
        single = init_layer_cache(cfg, spec, batch, seq, cross_seq)
        periods[f"pos{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), single
        )
    out["periods"] = periods
    return out


def _sp_constrain(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Sequence-parallel residual stream: under `seq_parallel`, the carried
    hidden states between layers shard their seq dim over `model` — the remat
    stash (n_periods per-layer inputs) then occupies 1/TP of the memory, and
    GSPMD inserts the Megatron-SP all-gather/reduce-scatter pair around each
    mixer block.  No-op when tracing without a mesh (smoke tests)."""
    if not cfg.seq_parallel or x.ndim < 3:
        return x
    try:
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(x, P(None, "model", None))
    except Exception:
        return x


def apply_stack(
    params: dict, cfg: ModelConfig, x: jax.Array, *,
    mode: str, positions=None, caches: dict | None = None, pos=None,
    causal: bool = True, cross_memory=None, mem_positions=None,
    capacities=None, pattern=None, prefix=None, remat: bool = True,
):
    """Returns (x, new_caches | None, aux)."""
    pattern = pattern if pattern is not None else cfg.layer_pattern
    prefix = prefix if prefix is not None else cfg.prefix_pattern
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix = []
    for i, spec in enumerate(prefix):
        c = caches["prefix"][i] if caches is not None else None
        x, nc, aux = apply_layer(
            params["prefix"][i], cfg, spec, x, mode=mode, positions=positions,
            cache=c, pos=pos, causal=causal, cross_memory=cross_memory,
            mem_positions=mem_positions, capacities=capacities,
        )
        aux_total = aux_total + aux
        new_prefix.append(nc)

    def body(carry, xs):
        # Decode carries the period caches and reads the layer index from
        # xs; the other modes return each layer's new cache as a scan output.
        h, aux_acc, stack = carry
        h = _sp_constrain(cfg, h)
        per_params, layer = xs if mode == "decode" else (xs, None)
        ncs = {}
        for i, spec in enumerate(pattern):
            c = stack[f"pos{i}"] if stack is not None else None
            h, nc, aux = apply_layer(
                per_params[f"pos{i}"], cfg, spec, h, mode=mode,
                positions=positions, cache=c, pos=pos, causal=causal,
                cross_memory=cross_memory, mem_positions=mem_positions,
                capacities=capacities, layer=layer,
            )
            aux_acc = aux_acc + aux
            if nc is not None:
                ncs[f"pos{i}"] = nc
        if mode == "decode":
            return (h, aux_acc, ncs), None
        return (h, aux_acc, None), (ncs if ncs else None)

    if remat and mode == "train":
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots"
            else None
        )
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    xs, stack = params["periods"], None
    if mode == "decode":
        n_periods = jax.tree.leaves(xs)[0].shape[0]
        xs, stack = (xs, jnp.arange(n_periods)), caches["periods"]
    (x, aux_total, stack), period_caches = jax.lax.scan(
        body, (x, aux_total, stack), xs, unroll=True if cfg.full_unroll else 1
    )
    if mode == "decode":
        period_caches = stack
    if mode == "train":
        return x, None, aux_total
    out_caches: dict[str, Any] = {"periods": period_caches}
    if prefix:
        out_caches["prefix"] = new_prefix
    return x, out_caches, aux_total
