"""Flash attention forward Pallas TPU kernel.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
"arbitrary" (sequential) — the online-softmax running max / sum / acc live
in VMEM scratch across kv steps and the output block is written on the
last kv step. GQA is zero-copy: the K/V BlockSpec index_map folds the
q-head -> kv-head mapping (h // group), so kv blocks are fetched from the
shared head without materializing the repeat.

Block shapes are (block_q, head_dim) / (block_kv, head_dim) — head_dim is
128 for every assigned arch, which is exactly the MXU lane width; block_q
and block_kv default to 128 (v5e MXU tile) and clamp to the sequence.

Causal and sliding-window masks are applied from absolute positions; with
causal=True, kv blocks strictly above the diagonal are skipped via
pl.when (no wasted MXU work). Optional logit softcap (tanh) is fused.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, window: int, softcap: float,
                block_q: int, block_kv: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # (bq, bkv)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                 # (bkv, d)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    cond = None
    if causal:   # skip blocks strictly above the diagonal
        cond = k_start <= q_start + block_q - 1
    if window:   # skip blocks entirely left of the window
        c2 = k_start + block_kv - 1 >= q_start - window + 1
        cond = c2 if cond is None else jnp.logical_and(cond, c2)
    if cond is None:
        _body()
    else:
        pl.when(cond)(_body)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, scale=None,
                        block_q: int = 128, block_kv: int = 128,
                        interpret: bool = False):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D). Returns (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale or 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    # pad sequences to block multiples (masked out by kpos < seq_len)
    pq = (-Sq) % block_q
    pkv = (-Skv) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    Sqp, Skvp = Sq + pq, Skv + pkv

    grid = (B, Hq, Sqp // block_q, Skvp // block_kv)
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, seq_len=Skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
