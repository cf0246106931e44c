"""A decode step writes exactly what the one-hot write did.

The model's decode carries the stacked caches through its layer loop and
scatters one row per slot into them.  The reference here walks the layers in
Python, each on its own slice of the cache, and writes with a select over
``arange(S) == pos[:, None]``: logits and every cache leaf must agree
bitwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, mla
from repro.models.layers import apply_norm, embed_tokens, lm_logits
from repro.models.model import Model, dec_pattern
from repro.models.transformer import apply_layer

B, S = 5, 16
# Slot 0 mid-sequence, slot 1 at 0, slot 2 at the last row, slots 3-4 idle
# (an idle slot writes a pad token at 0).
POS = np.array([6, 0, S - 1, 0, 0], np.int32)
LIVE = np.array([1, 1, 1, 0, 0], bool)


def _onehot_write(cache, new, pos, mode, layer=None):
    assert layer is None
    pos = jnp.broadcast_to(pos, cache.shape[:1])
    hit = jnp.arange(cache.shape[1])[None, :] == pos[:, None]
    hit = hit.reshape(hit.shape + (1,) * (cache.ndim - 2))
    return jnp.where(hit, new.astype(cache.dtype), cache)


def _reference_step(model, params, caches, toks, pos):
    cfg = model.cfg
    x = embed_tokens(params["embed"], toks, cfg)
    out = {}
    if cfg.prefix_pattern:
        out["prefix"] = []
        for i, spec in enumerate(cfg.prefix_pattern):
            x, c, _ = apply_layer(params["stack"]["prefix"][i], cfg, spec, x,
                                  mode="decode", cache=caches["prefix"][i],
                                  pos=pos)
            out["prefix"].append(c)
    n = jax.tree.leaves(caches["periods"])[0].shape[0]
    per_layer = {k: [] for k in caches["periods"]}
    for layer in range(n):
        for j, spec in enumerate(dec_pattern(cfg)):
            key = f"pos{j}"
            p = jax.tree.map(lambda a, i=layer: a[i], params["stack"]["periods"][key])
            c = jax.tree.map(lambda a, i=layer: a[i], caches["periods"][key])
            x, c, _ = apply_layer(p, cfg, spec, x, mode="decode", cache=c, pos=pos)
            per_layer[key].append(c)
    out["periods"] = {k: jax.tree.map(lambda *ls: jnp.stack(ls), *v)
                      for k, v in per_layer.items()}
    x = apply_norm(cfg, params["final_norm"], x)
    return lm_logits(params["embed"], x, cfg), out


@pytest.mark.parametrize("arch", [
    "qwen2-1.5b",           # GQA attention, q/k/v bias
    "deepseek-v2-236b",     # MLA, a prefix layer outside the scan
    "jamba-v0.1-52b",       # mamba state beside attention, MoE
    "mamba2-2.7b",          # recurrent state only, several scanned layers
    "seamless-m4t-medium",  # cross-attention caches pass through
])
@pytest.mark.parametrize("per_slot", [True, False], ids=["vector", "scalar"])
def test_decode_write_matches_onehot(arch, per_slot, monkeypatch):
    # float32: in bfloat16 the CPU compiler rounds a Python loop of layers
    # differently from the scanned one, whatever the write.
    cfg = get_config(arch, reduced=True)
    assert cfg.compute_dtype == "float32"
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    shape = jax.eval_shape(lambda: model.init_cache(B, S))
    leaves, tree = jax.tree.flatten(shape)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    caches = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves, strict=True)])
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, (B, 1))
    toks = jnp.asarray(np.where(LIVE[:, None], toks, 0), jnp.int32)
    pos = jnp.asarray(POS) if per_slot else jnp.int32(S // 2)

    logits, new = jax.jit(model.decode_step)(params, caches, toks, pos)
    with monkeypatch.context() as mp:
        mp.setattr(attention, "cache_write", _onehot_write)
        mp.setattr(mla, "cache_write", _onehot_write)
        ref_logits, ref = jax.jit(lambda *a: _reference_step(model, *a))(
            params, caches, toks, pos)

    np.testing.assert_array_equal(np.asarray(logits, np.float32),
                                  np.asarray(ref_logits, np.float32))
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
