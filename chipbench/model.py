"""A configuration file, read: the sizes both the program and the reference run.

A configuration is a JSON file of the published model's own keys (as its
``config.json`` names them), plus ``architecture`` (the family whose layer
equations apply), ``program_config`` (the program's registry entry that serves
it) and ``reduced``.  ``Dims`` is what the reference and the cost functions
need; ``program_config`` builds the program's ``ModelConfig`` at the file's
sizes.
"""

from __future__ import annotations

import dataclasses

# Per family: whether q/k/v carry a bias and whether q and k are RMS-normed
# per head before the rotary embedding.
ARCHITECTURES = {
    "qwen2": {"qkv_bias": True, "qk_norm": False},
    "qwen3": {"qkv_bias": False, "qk_norm": True},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    qkv_bias: bool
    qk_norm: bool
    dtype: str

    @property
    def layer_params(self) -> int:
        """Weights of one layer that a token's matmuls read."""
        attn = self.d_model * self.head_dim * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * self.d_model * self.d_ff

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


def dims(cfg: dict) -> Dims:
    arch = ARCHITECTURES[cfg["architecture"]]
    d_model, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qkv_bias = cfg.get("attention_bias", arch["qkv_bias"])
    return Dims(
        n_layers=cfg["num_hidden_layers"], d_model=d_model, n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d_model // heads),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(qkv_bias), qk_norm=arch["qk_norm"],
        dtype=cfg["torch_dtype"],
    )


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this file: the registry entry named
    by ``program_config`` (its kernels, layouts and options), at the sizes
    the file states."""
    from repro.configs.registry import get_config

    d = dims(cfg)
    return get_config(
        cfg["program_config"], n_layers=d.n_layers, d_model=d.d_model,
        n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, head_dim=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab_size, rope_theta=d.rope_theta,
        norm_eps=d.norm_eps, tie_embeddings=d.tie_embeddings,
        qkv_bias=d.qkv_bias, qk_norm=d.qk_norm, param_dtype=d.dtype,
        compute_dtype=d.dtype,
    )
