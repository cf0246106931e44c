"""Public SSD op: mamba2-layout handling, padding, chunked-jnp / kernel dispatch.

Three implementations, all equivalent:
  - ``ssd_scan_ref`` (ref.py): naive sequential scan — gold oracle.
  - ``ssd_chunked_jnp``: the SSD chunked algorithm in pure jnp — the model's
    default CPU/shardable path (same math as the kernel, vectorized over
    chunks with an outer lax.scan carrying the state).
  - Pallas kernel (mamba_scan.py): TPU hot path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..autotune import lookup
from .mamba_scan import ssd_scan as _ssd_kernel_call
from .ref import ssd_scan_ref

_DEFAULT_CHUNK = 128


def ssd_chunked_jnp(
    xdt: jax.Array, la: jax.Array, b: jax.Array, c: jax.Array, *, chunk: int = 128,
    h0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD in pure jnp: intra-chunk quadratic + scanned inter-chunk state."""
    bh, s, p = xdt.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0)))
        la = jnp.pad(la, ((0, 0), (0, pad)))  # la=0 => a=1, xdt=0: state preserved
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    xdt_c = xdt.reshape(bh, nc, chunk, p).astype(jnp.float32)
    la_c = la.reshape(bh, nc, chunk).astype(jnp.float32)
    b_c = b.reshape(bh, nc, chunk, n).astype(jnp.float32)
    c_c = c.reshape(bh, nc, chunk, n).astype(jnp.float32)
    cum = jnp.cumsum(la_c, axis=-1)                      # (bh, nc, c)
    # Intra-chunk (batched over chunks — no sequential dependence).
    g = jnp.einsum("bzin,bzjn->bzij", c_c, b_c)
    idx = jnp.arange(chunk)
    mask = idx[:, None] >= idx[None, :]
    logw = cum[..., :, None] - cum[..., None, :]
    s_mat = jnp.where(mask, g * jnp.exp(jnp.minimum(logw, 0.0)), 0.0)
    y_intra = jnp.einsum("bzij,bzjp->bzip", s_mat, xdt_c)
    # Inter-chunk state scan.
    chunk_decay = jnp.exp(cum[..., -1])                  # (bh, nc)
    wlast = jnp.exp(cum[..., -1:] - cum)                 # (bh, nc, c)
    h_contrib = jnp.einsum("bzcp,bzc,bzcn->bzpn", xdt_c, wlast, b_c)

    def step(h, inp):
        decay_z, contrib_z = inp                          # (bh,), (bh,p,n)
        h_out = decay_z[:, None, None] * h + contrib_z
        return h_out, h

    if h0 is None:
        h0 = jnp.zeros((bh, p, n), jnp.float32)
    h_final, h_prevs = jax.lax.scan(
        step, h0, (chunk_decay.transpose(1, 0), h_contrib.transpose(1, 0, 2, 3))
    )
    h_prevs = h_prevs.transpose(1, 0, 2, 3)               # state entering each chunk
    y_inter = jnp.exp(cum)[..., None] * jnp.einsum(
        "bzcn,bzpn->bzcp", c_c, h_prevs
    )
    y = (y_intra + y_inter).reshape(bh, nc * chunk, p)[:, :s]
    return y.astype(xdt.dtype), h_final


def ssd_chunked_grouped(
    xdt: jax.Array,   # (B, G, R, S, P)   R = heads per group
    la: jax.Array,    # (B, G, R, S)
    b: jax.Array,     # (B, G, S, N)      NOT head-repeated
    c: jax.Array,     # (B, G, S, N)
    *,
    chunk: int = 128,
    h0: jax.Array | None = None,   # (B, G, R, P, N)
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Group-aware chunked SSD.

    The Gram matrix (C_i . B_j) is per *group*, not per head — computing it
    grouped and broadcasting into the per-head decay product saves R x flops
    and R x bytes on the quadratic term (R = 80 for mamba2-2.7b), and B/C are
    never head-repeated (another R x on the linear terms).  Only the decayed
    score product and state tensors are inherently per-head (per-head dt)."""
    bsz, g, r, s, p = xdt.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        la = jnp.pad(la, ((0, 0), (0, 0), (0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    # Big tensors stay in the input compute dtype (bf16 in production); only
    # the decay chain (cumsum / exp) runs in f32 for stability.  The MXU-bound
    # einsums accumulate in f32 via preferred_element_type.
    mm = xdt.dtype
    f32 = jnp.float32
    xdt_c = xdt.reshape(bsz, g, r, nc, chunk, p)
    la_c = la.reshape(bsz, g, r, nc, chunk).astype(f32)
    b_c = b.reshape(bsz, g, nc, chunk, n)
    c_c = c.reshape(bsz, g, nc, chunk, n)
    cum = jnp.cumsum(la_c, axis=-1)                       # (B,G,R,nc,c) f32
    gram = jnp.einsum(
        "bgzin,bgzjn->bgzij", c_c, b_c, preferred_element_type=f32
    ).astype(mm)                                          # per-GROUP (B,G,nc,c,c)
    idx = jnp.arange(chunk)
    mask = idx[:, None] >= idx[None, :]
    logw = cum[..., :, None] - cum[..., None, :]          # (B,G,R,nc,c,c)
    decay = jnp.exp(jnp.minimum(logw, 0.0)).astype(mm)
    s_mat = jnp.where(mask, gram[:, :, None] * decay, 0)
    y_intra = jnp.einsum(
        "bgrzij,bgrzjp->bgrzip", s_mat, xdt_c, preferred_element_type=f32
    )
    chunk_decay = jnp.exp(cum[..., -1])                   # (B,G,R,nc) f32
    wlast = jnp.exp(cum[..., -1:] - cum).astype(mm)       # (B,G,R,nc,c)
    h_contrib = jnp.einsum(
        "bgrzcp,bgrzc,bgzcn->bgrzpn", xdt_c, wlast, b_c,
        preferred_element_type=f32,
    )

    def step(h, inp):
        decay_z, contrib_z = inp                          # (B,G,R), (B,G,R,P,N)
        return decay_z[..., None, None] * h + contrib_z, h

    if h0 is None:
        h0 = jnp.zeros((bsz, g, r, p, n), f32)
    h_final, h_prevs = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(chunk_decay, 3, 0), jnp.moveaxis(h_contrib, 3, 0)),
        unroll=True if unroll else 1,
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 3)                 # (B,G,R,nc,P,N)
    y_inter = jnp.exp(cum)[..., None] * jnp.einsum(
        "bgzcn,bgrzpn->bgrzcp", c_c, h_prevs.astype(mm),
        preferred_element_type=f32,
    )
    y = (y_intra + y_inter).reshape(bsz, g, r, nc * chunk, p)[:, :, :, :s]
    return y.astype(xdt.dtype), h_final


def ssd(
    x: jax.Array,       # (B, S, H, P)
    dt: jax.Array,      # (B, S, H)  (softplus already applied)
    a: jax.Array,       # (H,)       (negative)
    b: jax.Array,       # (B, S, G, N)
    c: jax.Array,       # (B, S, G, N)
    d: jax.Array | None = None,   # (H,) skip connection
    *,
    chunk: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    h0: jax.Array | None = None,   # (B, H, P, N)
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD layer core.  Returns (y (B,S,H,P), state (B,H,P,N)).
    ``chunk=None`` takes the autotune registry's winner for this shape bucket
    (``kernels/autotune.py``), falling back to 128."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if chunk is None:
        chunk = lookup("ssd", {"s": s, "p": p, "n": n}).get(
            "chunk", _DEFAULT_CHUNK)
    if h % g:
        raise ValueError(f"n_groups {g} must divide heads {h}")
    rep = h // g
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        # Kernel path: per-(B*H) grid; B/C repeat happens at HBM->VMEM stream
        # time on TPU (the kernel re-reads the group block per head, which the
        # BlockSpec index_map makes a VMEM-resident reuse, not an HBM copy).
        bb = jnp.repeat(b, rep, axis=2) if rep > 1 else b     # (B,S,H,N)
        cc = jnp.repeat(c, rep, axis=2) if rep > 1 else c
        xdt = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
        la = (dt * a[None, None, :]).transpose(0, 2, 1).reshape(bsz * h, s, 1)
        bf = bb.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
        cf = cc.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if h0 is not None:
            raise NotImplementedError("kernel path starts from zero state")
        pad = (-s) % chunk
        if pad:
            xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0)))
            la = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))
            bf = jnp.pad(bf, ((0, 0), (0, pad), (0, 0)))
            cf = jnp.pad(cf, ((0, 0), (0, pad), (0, 0)))
        y, state = _ssd_kernel_call(
            xdt, la, bf, cf, chunk=min(chunk, s + pad), interpret=interpret
        )
        y = y[:, :s].reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
        state = state.reshape(bsz, h, p, n)
    else:
        xdt_g = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(
            bsz, g, rep, s, p
        )
        la_g = (dt * a[None, None, :]).transpose(0, 2, 1).reshape(bsz, g, rep, s)
        bg = b.transpose(0, 2, 1, 3)                          # (B,G,S,N)
        cg = c.transpose(0, 2, 1, 3)
        h0g = None if h0 is None else h0.reshape(bsz, g, rep, p, n)
        y, state = ssd_chunked_grouped(xdt_g, la_g, bg, cg, chunk=chunk,
                                       h0=h0g, unroll=unroll)
        y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
        state = state.reshape(bsz, h, p, n)
    if d is not None:
        y = y + x * d[None, None, :, None].astype(x.dtype)  # keep compute dtype
    return y, state


__all__ = ["ssd", "ssd_chunked_jnp", "ssd_scan_ref"]
