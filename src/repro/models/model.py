"""Model facade: init / loss / prefill / decode for every architecture family.

Batch formats
  tokens mode : {"tokens": (B,S) i32, "targets": (B,S) i32, "loss_mask": (B,S) f32}
  embeds mode : {"embeds": (B,S,d), "positions": (B,S)|(B,3,S) i32, "targets", "loss_mask"}
  enc-dec     : {"src_embeds": (B,Ss,d), "tgt_tokens": (B,St) i32, "targets", "loss_mask"}

``loss_mask`` carries the homogenization grain weights: the loss is the
weighted token mean (sum w·ce / sum w), which keeps the gradient estimator
unbiased when the scheduler allots unequal token counts to workers.

Decode: ``decode_step(params, cache, inputs, pos)`` processes one token
against a fixed-capacity cache (dry-run decode cells: pos = seq_len - 1).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .config import LayerSpec, ModelConfig
from .layers import (
    apply_norm,
    dtype_of,
    embed_tokens,
    init_embedding,
    init_norm,
    lm_logits,
)
from .transformer import apply_stack, init_stack, init_stack_cache

ENC_PATTERN = (LayerSpec(mixer="attn", mlp="dense"),)


def dec_pattern(cfg: ModelConfig) -> tuple[LayerSpec, ...]:
    if not cfg.is_enc_dec:
        return cfg.layer_pattern
    return tuple(
        LayerSpec(mixer=s.mixer, mlp=s.mlp, cross_attn=True)
        for s in cfg.layer_pattern
    )


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg.validate()

    # ------------------------------------------------------------------ init
    def init(self, key) -> dict:
        cfg = self.cfg
        k_emb, k_stack, k_enc = jax.random.split(key, 3)
        params: dict[str, Any] = {
            "embed": init_embedding(k_emb, cfg),
            "final_norm": init_norm(cfg),
            "stack": init_stack(
                k_stack, cfg, pattern=dec_pattern(cfg),
                prefix=cfg.prefix_pattern, n_periods=cfg.n_periods,
            ),
        }
        if cfg.is_enc_dec:
            params["enc_stack"] = init_stack(
                k_enc, cfg, pattern=ENC_PATTERN, prefix=(),
                n_periods=cfg.encoder.n_layers,
            )
            params["enc_final_norm"] = init_norm(cfg)
        return params

    def abstract_params(self, seed: int = 0) -> dict:
        return jax.eval_shape(lambda: self.init(jax.random.key(seed)))

    # ----------------------------------------------------------------- embed
    def _embed(self, params, batch) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.is_enc_dec:
            tokens = batch["tgt_tokens"]
            x = embed_tokens(params["embed"], tokens, cfg)
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None], tokens.shape
            )
        elif cfg.input_mode == "embeds":
            x = batch["embeds"].astype(dtype_of(cfg.compute_dtype))
            positions = batch["positions"]
        else:
            tokens = batch["tokens"]
            x = embed_tokens(params["embed"], tokens, cfg)
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None], tokens.shape
            )
        return x, positions

    def encode(self, params, src_embeds: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = src_embeds.astype(dtype_of(cfg.compute_dtype))
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2]
        )
        x, _, _ = apply_stack(
            params["enc_stack"], cfg, x, mode="train", positions=positions,
            causal=False, pattern=ENC_PATTERN, prefix=(),
        )
        return apply_norm(cfg, params["enc_final_norm"], x)

    # ----------------------------------------------------------------- train
    def logits(self, params, batch, capacities=None) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        cross_memory = mem_pos = None
        if cfg.is_enc_dec:
            cross_memory = self.encode(params, batch["src_embeds"])
            mem_pos = jnp.broadcast_to(
                jnp.arange(cross_memory.shape[1])[None], cross_memory.shape[:2]
            )
        x, _, aux = apply_stack(
            params["stack"], cfg, x, mode="train", positions=positions,
            causal=True, cross_memory=cross_memory, mem_positions=mem_pos,
            capacities=capacities, pattern=dec_pattern(cfg),
        )
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(params["embed"], x, cfg), aux

    def hidden(self, params, batch, capacities=None) -> tuple[jax.Array, jax.Array]:
        """Final normed hidden states (pre-LM-head) + aux loss."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        cross_memory = mem_pos = None
        if cfg.is_enc_dec:
            cross_memory = self.encode(params, batch["src_embeds"])
            mem_pos = jnp.broadcast_to(
                jnp.arange(cross_memory.shape[1])[None], cross_memory.shape[:2]
            )
        x, _, aux = apply_stack(
            params["stack"], cfg, x, mode="train", positions=positions,
            causal=True, cross_memory=cross_memory, mem_positions=mem_pos,
            capacities=capacities, pattern=dec_pattern(cfg),
        )
        return apply_norm(cfg, params["final_norm"], x), aux

    def _chunked_ce(self, params, x, targets, w) -> jax.Array:
        """Fused chunked cross-entropy: never materializes (B, S, V) —
        sequence chunks of the hidden states hit the LM head one at a time and
        reduce immediately to (logsumexp, target-logit) pairs."""
        cfg = self.cfg
        c = cfg.ce_chunk
        b, s, d = x.shape
        pad = (-s) % c
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            w = jnp.pad(w, ((0, 0), (0, pad)))
        nc = (s + pad) // c
        table = (
            params["embed"]["head"]
            if "head" in params["embed"]
            else params["embed"]["table"].T
        )

        # Static Python loop (not lax.scan): identical HLO regardless of layer
        # count, so the dry-run cost extrapolation stays exact, and each
        # chunk's logits die before the next chunk materializes.
        total = jnp.zeros((), jnp.float32)
        for i in range(nc):
            xc = jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
            tc = jax.lax.dynamic_slice_in_dim(targets, i * c, c, axis=1)
            wc = jax.lax.dynamic_slice_in_dim(w, i * c, c, axis=1)
            lg = jnp.einsum("bsd,dv->bsv", xc, table).astype(jnp.float32)
            if cfg.padded_vocab != cfg.vocab_size:
                lg = lg.at[..., cfg.vocab_size :].set(-1e30)
            lse = jax.nn.logsumexp(lg, axis=-1)
            tlog = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
            total = total + jnp.sum((lse - tlog) * wc)
        return total

    def loss(self, params, batch, capacities=None) -> tuple[jax.Array, dict]:
        w = batch["loss_mask"].astype(jnp.float32)
        wsum = jnp.maximum(jnp.sum(w), 1.0)
        if self.cfg.ce_chunk > 0:
            x, aux = self.hidden(params, batch, capacities)
            ce = self._chunked_ce(params, x, batch["targets"], w) / wsum
        else:
            logits, aux = self.logits(params, batch, capacities)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(
                lp, batch["targets"][..., None], axis=-1
            )[..., 0]
            ce = jnp.sum(nll * w) / wsum
        loss = ce + aux
        return loss, {"loss": loss, "ce": ce, "aux": aux, "tokens": wsum}

    # ----------------------------------------------------------------- serve
    def init_cache(self, batch_size: int, seq: int, cross_seq: int | None = None):
        cfg = self.cfg
        return init_stack_cache(
            cfg, batch_size, seq, pattern=dec_pattern(cfg),
            prefix=cfg.prefix_pattern, n_periods=cfg.n_periods,
            cross_seq=cross_seq,
        )

    def prefill(self, params, batch, capacities=None, last_pos=None):
        """Full-prompt forward.  Returns (last-token logits, caches).

        ``last_pos`` selects which position's logits to return (default: the
        final one).  Bucketed prefill pads prompts to a fixed length on the
        right; causality keeps every valid position's activations exact, so
        the true last-token logits live at ``last_pos = L - 1``, not -1."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        cross_memory = mem_pos = None
        if cfg.is_enc_dec:
            cross_memory = self.encode(params, batch["src_embeds"])
            mem_pos = jnp.broadcast_to(
                jnp.arange(cross_memory.shape[1])[None], cross_memory.shape[:2]
            )
        x, caches, _ = apply_stack(
            params["stack"], cfg, x, mode="prefill", positions=positions,
            causal=True, cross_memory=cross_memory, mem_positions=mem_pos,
            capacities=capacities, pattern=dec_pattern(cfg),
        )
        if last_pos is None:
            x = x[:, -1:]
        else:
            x = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(params["embed"], x, cfg), caches

    def decode_step(self, params, caches, inputs, pos, capacities=None):
        """One-token decode.  ``inputs``: (B,1) tokens or (B,1,d)/(B,3,1)-pos
        embeds per input_mode.  Returns (logits (B,1,V), new caches)."""
        cfg = self.cfg
        if cfg.input_mode == "embeds" and not cfg.is_enc_dec:
            x = inputs["embeds"].astype(dtype_of(cfg.compute_dtype))
            positions = inputs["positions"]
        else:
            tok = inputs["tokens"] if isinstance(inputs, dict) else inputs
            x = embed_tokens(params["embed"], tok, cfg)
            positions = None  # attention uses `pos` scalar for rope
        x, caches, _ = apply_stack(
            params["stack"], cfg, x, mode="decode", positions=positions,
            caches=caches, pos=pos, capacities=capacities,
            pattern=dec_pattern(cfg),
        )
        x = apply_norm(cfg, params["final_norm"], x)
        return lm_logits(params["embed"], x, cfg), caches
