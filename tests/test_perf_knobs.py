"""Perf-iteration knobs must preserve exact (or bounded-drift) semantics.

Compile-heavy (~30s of jit across knob variants): out of the tier-1 default
run, exercised via `pytest -m slow` (see pytest.ini)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.models import LayerSpec, Model, ModelConfig


def base_cfg(**kw) -> ModelConfig:
    d = dict(
        name="knobs", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_ff=64, vocab_size=60, head_dim=8, vocab_pad_to=64,
        layer_pattern=(LayerSpec("attn", "dense"),),
        param_dtype="float32", compute_dtype="float32",
        rope_theta=1e4, use_pallas=False,
    )
    d.update(kw)
    return ModelConfig(**d)


def lm_batch(b=2, s=13, v=60, seed=0):
    rng = np.random.default_rng(seed)
    t = jnp.asarray(rng.integers(0, v, (b, s)))
    return {
        "tokens": t,
        "targets": jnp.roll(t, -1, 1),
        "loss_mask": jnp.ones((b, s), jnp.float32),
    }


def test_chunked_ce_exact():
    cfg = base_cfg()
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch()
    l0, _ = m.loss(p, batch)
    for c in (4, 5, 13, 32):
        l1, _ = Model(dataclasses.replace(cfg, ce_chunk=c)).loss(p, batch)
        assert abs(float(l0) - float(l1)) < 1e-5, (c, float(l0), float(l1))


def test_chunked_ce_gradients_match():
    cfg = base_cfg()
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch()
    g0 = jax.grad(lambda p: m.loss(p, batch)[0])(p)
    g1 = jax.grad(
        lambda p: Model(dataclasses.replace(cfg, ce_chunk=4)).loss(p, batch)[0]
    )(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_remat_policies_same_loss():
    cfg = base_cfg()
    p = Model(cfg).init(jax.random.key(0))
    batch = lm_batch()
    l_n, _ = Model(cfg).loss(p, batch)
    l_d, _ = Model(dataclasses.replace(cfg, remat_policy="dots")).loss(p, batch)
    assert abs(float(l_n) - float(l_d)) < 1e-6
    g = jax.grad(
        lambda p: Model(dataclasses.replace(cfg, remat_policy="dots")).loss(p, batch)[0]
    )(p)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


def _decode_all(m, p, batch, s):
    caches = m.init_cache(2, s)
    out = None
    for i in range(s):
        out, caches = m.decode_step(p, caches, batch["tokens"][:, i : i + 1], jnp.int32(i))
    return out


def test_cache_dtype_bf16_bounded_drift():
    cfg = base_cfg()
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch()
    ref, _ = m.logits(p, batch)
    out = _decode_all(Model(dataclasses.replace(cfg, cache_dtype="bfloat16")), p, batch, 13)
    drift = float(jnp.max(jnp.abs(out[:, 0] - ref[:, -1])))
    assert drift < 0.05, drift


def test_onehot_cache_update_exact():
    cfg = base_cfg()
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch()
    ref = _decode_all(m, p, batch, 13)
    out = _decode_all(Model(dataclasses.replace(cfg, cache_update="onehot")), p, batch, 13)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=1e-5, atol=1e-5
    )


def test_full_unroll_same_numerics():
    cfg = base_cfg()
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = lm_batch()
    l0, _ = m.loss(p, batch)
    l1, _ = Model(dataclasses.replace(cfg, full_unroll=True)).loss(p, batch)
    assert abs(float(l0) - float(l1)) < 1e-6


def test_grouped_gqa_vs_mha_consistency():
    """GQA with Hkv == Hq must equal plain MHA math (group size 1 path)."""
    cfg = base_cfg(n_heads=4, n_kv_heads=4)
    m = Model(cfg)
    p = m.init(jax.random.key(1))
    batch = lm_batch()
    logits, _ = m.logits(p, batch)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
