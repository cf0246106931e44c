"""Operations and bytes the served work requires, computed from shapes.

These are the least the chip has to do for a call, whatever the program does
beyond it: the roofline of a call is the larger of operations over the peak
rate and bytes over the peak bandwidth (``least_seconds``).  A count of
multiply-adds is two operations.  Weights and cache are read once; padding,
re-reads and recomputation are the program's, not the work's.
"""

from __future__ import annotations

from .model import Dims


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline time of a call, and which of the two bounds it."""
    t_c, t_m = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def head_params(d: Dims) -> int:
    return d.d_model * d.vocab_size


def decode_step(d: Dims, positions) -> tuple[float, float]:
    """One decode step of ``len(positions)`` slots, slot ``i`` writing its
    new key and value at ``positions[i]`` and attending over positions
    ``0..positions[i]``.  Bytes: every weight a token multiplies (the layers
    and the output head), the live cache, and the written position."""
    b = len(positions)
    ctx = sum(p + 1 for p in positions)
    kv_row = 2 * d.n_kv_heads * d.head_dim * d.n_layers      # K and V, all layers
    flops = (2 * b * (d.n_layers * d.layer_params + head_params(d))
             + 4 * d.n_layers * d.n_heads * d.head_dim * ctx)
    nbytes = d.dtype_bytes * (d.n_layers * d.layer_params + head_params(d)
                              + kv_row * (ctx - b) + kv_row * b)
    return float(flops), float(nbytes)


def prefill(d: Dims, length: int) -> float:
    """Model operations of a prefill of a ``length``-token prompt: every
    layer's matmuls for each token, causal attention, and the output head
    for the last token."""
    attn = 4 * d.n_layers * d.n_heads * d.head_dim * length * (length + 1) // 2
    return float(2 * length * d.n_layers * d.layer_params + attn + 2 * head_params(d))


def prefill_attention(length: int, q_heads: int, kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> tuple[float, float]:
    """One layer's prefill attention over a ``length``-token prompt: causal
    QK^T and PV for ``q_heads`` heads; q, k and v read once and the output
    written once."""
    flops = 4 * q_heads * head_dim * length * (length + 1) // 2
    nbytes = itemsize * length * head_dim * (2 * q_heads + 2 * kv_heads)
    return float(flops), float(nbytes)
