"""Mamba-2 SSD chunked-scan Pallas kernel (state-space duality form).

Recurrence per head:  h_i = a_i * h_{i-1} + xdt_i ⊗ B_i,   y_i = h_i · C_i
with a_i = exp(dt_i * A) ∈ (0,1].  The SSD trick splits time into chunks:
inside a chunk the quadratic "attention" form runs on the MXU
(S_mat = (C Bᵀ) ⊙ decay-mask), while a (P,N) state carried across chunks in
VMEM scratch handles the inter-chunk recurrence.  Grid is (B*H, S/chunk) with
the chunk axis sequential ("arbitrary") — exactly the HBM→VMEM blocking the
TPU memory hierarchy wants: each chunk's xdt/B/C tiles stream through VMEM
once, the state never leaves.

Inputs are pre-flattened to (B*H, S, ·) and dt-premultiplied by ops.py; decay
logs ``la = dt * A <= 0`` keep every exp() argument non-positive (stable).
``la`` is carried as a (B*H, S, 1) column: Mosaic requires a block's last two
dims to divide (8, 128) or equal the array's, which a (1, chunk) block of a
2-D array does not.  Mosaic has no cumsum either, so the in-chunk prefix sum
is a lower-triangular matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, la_ref, b_ref, c_ref, y_ref, state_ref, h_ref, *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xdt = xdt_ref[0].astype(jnp.float32)          # (c, P)
    la = la_ref[0].astype(jnp.float32)            # (c, 1)
    bmat = b_ref[0].astype(jnp.float32)           # (c, N)
    cmat = c_ref[0].astype(jnp.float32)           # (c, N)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (ii >= jj).astype(jnp.float32)
    cum = jax.lax.dot_general(                    # (c, 1) inclusive prefix logs
        # HIGHEST: a bf16 pass would round the logs before exp() amplifies.
        tri, la, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    # Intra-chunk quadratic form: S[i,j] = (C_i·B_j) exp(cum_i - cum_j), j<=i.
    g = jax.lax.dot_general(                      # (c, c)
        cmat, bmat, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    logw = cum - cum.T
    s_mat = jnp.where(ii >= jj, g * jnp.exp(jnp.minimum(logw, 0.0)), 0.0)
    y_intra = jax.lax.dot_general(                # (c, P)
        s_mat, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # Inter-chunk: y_i += exp(cum_i) * C_i @ h0^T ; h0 is (P, N).
    h0 = h_ref[...]
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        cmat, h0, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # State update: h = exp(cum_last) h0 + (xdt ⊙ exp(cum_last - cum))ᵀ B.
    # (1, 1) = cum[-1]; Mosaic cannot broadcast a slice of cum's last row.
    last = jnp.sum(la, axis=0, keepdims=True)
    wlast = jnp.exp(last - cum)                   # (c, 1)
    h_new = jnp.exp(last) * h0 + jax.lax.dot_general(
        xdt * wlast, bmat, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h_ref[...] = h_new
    state_ref[0] = h_new.astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    xdt: jax.Array,   # (BH, S, P) — dt-premultiplied input
    la: jax.Array,    # (BH, S, 1) — log decay dt*A (<= 0)
    b: jax.Array,     # (BH, S, N)
    c: jax.Array,     # (BH, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (BH,S,P), final_state (BH,P,N))."""
    bh, s, p = xdt.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"S={s} not divisible by chunk={chunk}; ops.py pads")
    n_chunks = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    y, state = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, p, n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), xdt.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        **params,
    )(xdt, la, b, c)
    return y, state
