"""Mamba-2 SSD Pallas kernel: fused chunked state-space scan.

TPU-native layout of the SSD algorithm [arXiv:2405.21060 §6]: the grid is
(batch, heads, chunks) with the CHUNK dimension sequential ("arbitrary");
the inter-chunk recurrent state (P x N) lives in VMEM scratch and carries
across chunk steps — so the whole sequence scan is ONE kernel launch, with
the quadratic intra-chunk block hitting the MXU and zero HBM traffic for
the (Q x Q) decay-masked score tile (the tile that dominates the XLA
lowering's memory term).

Per chunk step (all in VMEM, fp32):
  seg   = cumsum(dt * A)                         (Q,)
  L     = exp(seg_i - seg_j) * tril              (Q, Q)
  y     = ((C Bᵀ) ⊙ L) (dt ⊙ x)                  intra-chunk, MXU
  y    += (C state_in) ⊙ exp(seg)                inter-chunk contribution
  state = exp(total) * state_in + Σ_j exp(total - seg_j) dt_j B_j xᵀ_j
  out  += D ⊙ x                                  skip
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    A = a_ref[...]                               # (1,) negative decay rate
    B = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)          # (Q, N)
    D = d_ref[...]                               # (1,)

    dA = dt[:, 0] * A[0]                         # (Q,)
    seg = jnp.cumsum(dA)                         # (Q,)
    total = seg[-1]

    # intra-chunk: ((C B^T) ⊙ L) (dt ⊙ x)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    li = seg[:, None] - seg[None, :]
    tril = (jax.lax.broadcasted_iota(jnp.int32, cb.shape, 0)
            >= jax.lax.broadcasted_iota(jnp.int32, cb.shape, 1))
    L = jnp.where(tril, jnp.exp(li), 0.0)
    scores = cb * L * dt[:, 0][None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q,P)

    # inter-chunk: C · state_in, decayed to each position
    y += jnp.exp(seg)[:, None] * jax.lax.dot_general(
        C, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                        # (Q,P)

    # skip connection
    y += x * D[0]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state = e^total * state_in + Σ_j w_j x_j ⊗ B_j
    w = jnp.exp(total - seg) * dt[:, 0]                            # (Q,)
    new_contrib = jax.lax.dot_general(
        x * w[:, None], B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                        # (P,N)
    state_ref[...] = jnp.exp(total) * state_ref[...] + new_contrib


def ssd_fwd(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool = False):
    """x: (Bz,S,H,P); dt: (Bz,S,H) softplus'd; A,D: (H,); B,C: (Bz,S,H,N)
    (groups pre-broadcast). Returns y: (Bz,S,H,P)."""
    Bz, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk
    # layout: (Bz, H, S, *) so (chunk, feature) tiles are contiguous
    xt = jnp.swapaxes(x, 1, 2)
    dtt = jnp.swapaxes(dt, 1, 2)[..., None]       # (Bz,H,S,1)
    Bt = jnp.swapaxes(B, 1, 2)
    Ct = jnp.swapaxes(C, 1, 2)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(Bz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bz, H, Sp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bt, Ct, D.astype(jnp.float32))
    return jnp.swapaxes(out, 1, 2)[:, :S]
