"""Grouped expert GEMM Pallas kernel (MegaBlocks-style, capacity layout).

Computes out[e] = act(x[e] @ wi_gate[e]) * (x[e] @ wi_up[e]) @ wo[e] is the
full expert MLP; this kernel is the batched-GEMM primitive it decomposes
into: out[e] = x[e] @ w[e] for E experts with per-expert (C, d) x (d, f)
tiles. Grid: (E, C_blocks, F_blocks, D_blocks) with the contraction
dimension sequential, accumulating in VMEM scratch — every expert's tile
lands on the MXU at 128 alignment, and the expert dim is a parallel grid
axis (EP-sharded experts each launch their local slice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_gemm(x, w, *, block_c: int = 128, block_f: int = 128,
                 block_d: int = 256, interpret: bool = False):
    """x: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    E, C, d = x.shape
    f = w.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    pc, pf, pd = (-C) % block_c, (-f) % block_f, (-d) % block_d
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
    Cp, fp, dp = C + pc, f + pf, d + pd

    out = pl.pallas_call(
        _gemm_kernel,
        grid=(E, Cp // block_c, fp // block_f, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :C, :f]
