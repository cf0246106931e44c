"""Seeded weights, made by the benchmark, and their packing into the program.

The benchmark owns the weights: ``make_weights`` draws every tensor of the
architecture from the seed, on the device, in one jitted call, in the type
they are served in.  The layout is the plain one of the published model
(``layers`` stacked on a leading axis; heads as published, not padded).  The
reference reads this layout.  ``pack`` hands the same arrays to the program
in its own parameter tree, adding only what the program's layout needs (heads
padded for tensor parallelism, whose output projection is zero, so they add
nothing to the result).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .model import Dims


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, also one wider than 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _shapes(d: Dims) -> dict:
    L, D, H, K, Dh, F = d.n_layers, d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    layers = {
        "norm1": (L, D), "norm2": (L, D),
        "wq": (L, D, H, Dh), "wk": (L, D, K, Dh), "wv": (L, D, K, Dh),
        "wo": (L, H, Dh, D),
        "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
    }
    if d.qkv_bias:
        layers.update(bq=(L, H, Dh), bk=(L, K, Dh), bv=(L, K, Dh))
    if d.qk_norm:
        layers.update(q_norm=(L, Dh), k_norm=(L, Dh))
    out = {"embed": (d.vocab_size, D), "final_norm": (D,), "layers": layers}
    if not d.tie_embeddings:
        out["head"] = (D, d.vocab_size)
    return out


def _scale(name: str, shape: tuple, d: Dims) -> tuple[float, float]:
    """(mean, std) of each tensor: fan-in scaled projections, norm scales
    near one, small biases, embeddings at 0.02."""
    if name in ("norm1", "norm2", "final_norm", "q_norm", "k_norm"):
        return 1.0, 0.1
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.1
    if name == "embed":
        return 0.0, 0.02
    fan_in = {"wo": d.n_heads * d.head_dim, "w_down": d.d_ff}.get(name, d.d_model)
    return 0.0, fan_in ** -0.5


def make_weights(d: Dims, seed: int) -> dict:
    """Every weight of the model from ``seed``: one jitted call, on the
    default device, in ``d.dtype``."""
    shapes = _shapes(d)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(d.dtype)

    def init(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            mean, std = _scale(name, shape, d)
            out.append((mean + std * jax.random.normal(k, shape, jnp.float32)).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.block_until_ready(jax.jit(init)(seed_key(seed)))


def _pad_heads(x: jax.Array, axis: int, n_kv: int, n_pad: int) -> jax.Array:
    """Spread ``x``'s query heads over ``n_pad`` slots so that head ``j`` of
    kv group ``g`` sits where the program's grouped attention reads group
    ``g``; the new slots are zero."""
    h = x.shape[axis]
    g_real, g_pad = h // n_kv, n_pad // n_kv
    shape = x.shape[:axis] + (n_kv, g_real) + x.shape[axis + 1:]
    x = x.reshape(shape)
    widths = [(0, 0)] * x.ndim
    widths[axis + 1] = (0, g_pad - g_real)
    x = jnp.pad(x, widths)
    return x.reshape(x.shape[:axis] + (n_pad,) + x.shape[axis + 2:])


def pack(weights: dict, d: Dims, target) -> dict:
    """The program's parameter tree (``target``: its ``eval_shape``) filled
    from ``weights``.  Leaves that need no change are the same arrays."""
    lw = weights["layers"]
    n_q = target["stack"]["periods"]["pos0"]["attn"]["wq"].shape[2]
    padded = {}
    if n_q != d.n_heads:
        axes = {k: a for k, a in (("wq", 2), ("wo", 1), ("bq", 1)) if k in lw}
        padded = jax.jit(lambda ws: {
            k: _pad_heads(w, axes[k], d.n_kv_heads, n_q) for k, w in ws.items()
        })({k: lw[k] for k in axes})
    vocab_pad = target["embed"]["table"].shape[0] - d.vocab_size

    def fill(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        name = keys[-1]
        if keys[0] == "embed":
            if name == "table":
                w = weights["embed"]
                return jnp.pad(w, ((0, vocab_pad), (0, 0))) if vocab_pad else w
            w = weights["head"]
            return jnp.pad(w, ((0, 0), (0, vocab_pad))) if vocab_pad else w
        if keys[0] == "final_norm":
            return weights["final_norm"]
        if keys[:2] != ["stack", "periods"] or keys[2] != "pos0":
            raise ValueError(f"no weight for program leaf {keys}")
        # .../norm1/scale -> norm1; .../attn/wq -> wq; .../mlp/w_up -> w_up
        src = keys[-2] if name == "scale" else name
        w = padded.get(src, lw[src])
        if w.shape != leaf.shape or w.dtype != leaf.dtype:
            raise ValueError(f"program leaf {keys} is {leaf.shape} {leaf.dtype}, "
                             f"weights give {w.shape} {w.dtype}")
        return w

    return jax.tree_util.tree_map_with_path(fill, target)
