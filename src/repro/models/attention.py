"""Attention: GQA / MLA, full / sliding-window / chunked / Pallas-flash.

Three interchangeable inner implementations (``cfg.attn_impl``):

* ``reference`` — materialises the (Sq, Skv) logits; used for small tests
  and as the oracle.
* ``chunked``   — lax.scan over KV chunks with online softmax; never
  materialises the full score matrix. This is the dry-run / production
  lowering path (pure jnp, shards under SPMD).
* ``flash``     — Pallas TPU kernel (repro.kernels.flash_attention);
  validated in interpret mode on CPU.

KV caches are pre-allocated ``(B, S_cache, n_kv, hd)`` buffers updated with
``dynamic_update_slice``; sliding-window layers allocate only the window and
write modulo the window size. MLA caches the compressed latent
``(B, S, kv_lora + rope_dim)`` and decodes via the weight-absorption trick.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import apply_norm, apply_rope, apply_mrope, dense_init, init_norm

NEG_INF = -1e30


# =============================================================== core softmax
def _mask_bias(q_pos, kv_pos, causal: bool, window: int, kv_len_valid=None):
    """(…, Sq, Skv) additive bias from position comparisons."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    # kp < 0 marks unwritten ring-buffer slots (decode warm-up) — always masked.
    ok = kp >= 0
    if causal:
        ok = ok & (kp <= qp)
    if window:
        ok = ok & (qp - kp < window)
    if kv_len_valid is not None:
        ok = ok & (kp < kv_len_valid)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap else x


def _repeat_kv(k, v, n_heads: int):
    """Broadcast GQA KV to the full (possibly padded) q-head count.

    Under SPMD this keeps the head axis cleanly shardable on `model` even
    when n_kv_heads doesn't divide the axis (the replicated KV is sliced
    per-device by the broadcast); einsum FLOPs are identical to grouped
    attention.

    When Hq is padded past a non-dividing Hkv (qwen1.5-4b: 20 MHA heads
    padded to 32 q heads), real heads keep their exact kv (h -> min(h,
    Hkv-1)); the zero-weight padded heads borrow the last kv head. This
    keeps the KV cache at its true head count — no padded-head storage.
    """
    Hkv = k.shape[2]
    if Hkv == n_heads:
        return k, v
    if n_heads % Hkv == 0:
        rep = n_heads // Hkv
        return (jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2))
    idx = jnp.minimum(jnp.arange(n_heads), Hkv - 1)
    return k[:, :, idx, :], v[:, :, idx, :]


def attention_reference(q, k, v, q_pos, kv_pos, *, causal, window=0, softcap=0.0,
                        scale=None, kv_len_valid=None):
    """q: (B,Sq,Hq,D) k/v: (B,Skv,Hkv,D[v]). Returns (B,Sq,Hq,Dv)."""
    B, Sq, Hq, D = q.shape
    k, v = _repeat_kv(k, v, Hq)
    scale = scale or (1.0 / math.sqrt(D))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = _softcap(logits, softcap)
    bias = _mask_bias(q_pos, kv_pos, causal, window, kv_len_valid)  # (B?,Sq,Skv)
    while bias.ndim < logits.ndim:
        bias = bias[:, None]
    logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _make_flash_chunked(causal: bool, window: int, softcap: float,
                        chunk: int):
    """Flash-style chunked attention with a custom VJP.

    The forward scans KV chunks with an online softmax; the backward
    *recomputes* each chunk's probabilities from the saved logsumexp
    (FlashAttention's memory trick). Residuals are O(B*H*Sq*(D+1)) — the
    plain-autodiff scan would otherwise stash O(Sq*chunk) probabilities per
    chunk per layer, which is what blows HBM at 32k prefill / 4k train.

    Assumes Hq == Hkv (callers repeat GQA KV; autodiff of the repeat sums
    group gradients back).
    """

    def _chunks(k, v, kv_pos, B):
        Skv = k.shape[1]
        c = min(chunk, Skv)
        n = -(-Skv // c)
        pad = n * c - Skv
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            kv_pos = jnp.pad(kv_pos, [(0, 0)] * (kv_pos.ndim - 1) + [(0, pad)],
                             constant_values=2**30)
        H, D = k.shape[2], k.shape[3]
        Dv = v.shape[3]
        kc = jnp.moveaxis(k.reshape(B, n, c, H, D), 1, 0)
        vc = jnp.moveaxis(v.reshape(B, n, c, H, Dv), 1, 0)
        pc = jnp.moveaxis(kv_pos.reshape(kv_pos.shape[:-1] + (n, c)), -2, 0)
        return kc, vc, pc, n, c

    def _bias(q_pos, p_i, ndim):
        bias = _mask_bias(q_pos, p_i, causal, window, None)
        while bias.ndim < ndim:
            bias = bias[:, None]
        return bias

    def fwd_impl(q, k, v, q_pos, kv_pos, scale):
        B, Sq, Hq, D = q.shape
        Dv = v.shape[-1]
        kc, vc, pc, n, c = _chunks(k, v, kv_pos, B)
        qs = (q.astype(jnp.float32) * scale)

        @jax.named_scope("pallas_flash_attention")
        def body(carry, xs):
            m, l, acc = carry
            k_i, v_i, p_i = xs
            logits = jnp.einsum("bqhd,bkhd->bhqk", qs, k_i.astype(jnp.float32))
            logits = _softcap(logits, softcap) + _bias(q_pos, p_i, 4)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
        a0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))            # (B,H,Sq)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype), lse

    @jax.custom_vjp
    def flash(q, k, v, q_pos, kv_pos, scale):
        return fwd_impl(q, k, v, q_pos, kv_pos, scale)[0]

    def flash_fwd(q, k, v, q_pos, kv_pos, scale):
        out, lse = fwd_impl(q, k, v, q_pos, kv_pos, scale)
        return out, (q, k, v, q_pos, kv_pos, scale, out, lse)

    def flash_bwd(res, g):
        q, k, v, q_pos, kv_pos, scale, out, lse = res
        B, Sq, Hq, D = q.shape
        kc, vc, pc, n, c = _chunks(k, v, kv_pos, B)
        qs = q.astype(jnp.float32) * scale
        go = jnp.moveaxis(g.astype(jnp.float32), 2, 1)       # (B,H,Sq,Dv)
        oo = jnp.moveaxis(out.astype(jnp.float32), 2, 1)
        delta = jnp.sum(go * oo, axis=-1)                    # (B,H,Sq)

        @jax.named_scope("pallas_flash_attention")
        def body(dq_acc, xs):
            k_i, v_i, p_i = xs
            raw = jnp.einsum("bqhd,bkhd->bhqk", qs, k_i.astype(jnp.float32))
            capped = _softcap(raw, softcap)
            logits = capped + _bias(q_pos, p_i, 4)
            p = jnp.exp(logits - lse[..., None])             # (B,H,Sq,c)
            dv_i = jnp.einsum("bhqk,bhqd->bkhd", p, go)
            dp = jnp.einsum("bhqd,bkhd->bhqk", go, v_i.astype(jnp.float32))
            ds = p * (dp - delta[..., None])
            if softcap:
                ds = ds * (1.0 - jnp.square(capped / softcap))
            dq_i = jnp.einsum("bhqk,bkhd->bqhd", ds, k_i.astype(jnp.float32))
            dk_i = jnp.einsum("bhqk,bqhd->bkhd", ds, qs)
            return dq_acc + dq_i, (dk_i, dv_i)

        dq0 = jnp.zeros((B, Sq, Hq, D), jnp.float32)
        dq, (dk_c, dv_c) = jax.lax.scan(body, dq0, (kc, vc, pc))
        dq = (dq * scale).astype(q.dtype)
        Skv = k.shape[1]
        # dk needs no extra scale: qs already carries it
        dk = jnp.moveaxis(dk_c, 0, 1).reshape(B, n * c, Hq, D)[:, :Skv]
        dk = dk.astype(k.dtype)
        dv = jnp.moveaxis(dv_c, 0, 1).reshape(B, n * c, Hq, -1)[:, :Skv]
        dv = dv.astype(v.dtype)
        import numpy as np
        zp = lambda x: np.zeros(x.shape, jax.dtypes.float0)
        return dq, dk, dv, zp(q_pos), zp(kv_pos), None

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def attention_chunked(q, k, v, q_pos, kv_pos, *, causal, window=0, softcap=0.0,
                      scale=None, chunk=1024, kv_len_valid=None):
    """Online-softmax over KV chunks (flash-style, pure jnp + lax.scan)."""
    B, Sq, Hq, D = q.shape
    k, v = _repeat_kv(k, v, Hq)
    scale = scale or (1.0 / math.sqrt(D))
    if kv_len_valid is not None:
        # rare path (masked decode); plain reference math
        return attention_reference(q, k, v, q_pos, kv_pos, causal=causal,
                                   window=window, softcap=softcap, scale=scale,
                                   kv_len_valid=kv_len_valid)
    fn = _make_flash_chunked(bool(causal), int(window), float(softcap),
                             int(chunk))
    return fn(q, k, v, q_pos, kv_pos, scale)


def attention_flash(q, k, v, q_pos, kv_pos, *, causal, window=0, softcap=0.0,
                    scale=None, kv_len_valid=None, interpret=None):
    from repro.kernels.flash_attention import ops as fa_ops
    if interpret is None:
        # Pallas TPU kernels execute natively on TPU and in interpret mode
        # on the CPU (tests); any other backend is refused rather than
        # silently interpreted.
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise NotImplementedError(
                f"flash attention runs on tpu (or interpreted on cpu), "
                f"not on {backend!r}")
        interpret = backend == "cpu"
    return fa_ops.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        interpret=interpret)


def attention_core(q, k, v, q_pos, kv_pos, cfg: ModelConfig, *, causal, window=0,
                   softcap=0.0, scale=None, kv_len_valid=None):
    impl = cfg.attn_impl
    if q.shape[1] == 1:
        # decode: logits are (B,H,1,S) — elementwise over the (possibly
        # sequence-sharded) cache; SPMD inserts the partial-softmax
        # reductions (flash-decoding on the mesh). No scan needed.
        impl = "reference"
    if impl == "flash" and kv_len_valid is None and window == 0:
        return attention_flash(q, k, v, q_pos, kv_pos, causal=causal,
                               window=window, softcap=softcap, scale=scale)
    if impl in ("chunked", "flash"):
        return attention_chunked(q, k, v, q_pos, kv_pos, causal=causal,
                                 window=window, softcap=softcap, scale=scale,
                                 chunk=cfg.attn_chunk, kv_len_valid=kv_len_valid)
    return attention_reference(q, k, v, q_pos, kv_pos, causal=causal,
                               window=window, softcap=softcap, scale=scale,
                               kv_len_valid=kv_len_valid)


# ========================================================================= GQA
def init_attention(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 6)
    nq, nkv, hd, d = cfg.nq, cfg.nkv, cfg.hd, cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, (nq, hd), cfg.pdtype),
        "wk": dense_init(ks[1], d, (nkv, hd), cfg.pdtype),
        "wv": dense_init(ks[2], d, (nkv, hd), cfg.pdtype),
        "wo": dense_init(ks[3], nq * hd, d, cfg.pdtype).reshape(nq, hd, d),
    }
    if cfg.n_heads != nq:  # zero the padded q heads: function preserving
        mask = (jnp.arange(nq) < cfg.n_heads).astype(p["wq"].dtype)
        p["wq"] = p["wq"] * mask[None, :, None]
        p["wo"] = p["wo"] * mask[:, None, None]
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((nkv, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((nkv, hd), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg, hd)
        p["k_norm"] = init_norm(cfg, hd)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions, theta: float):
    q = jnp.einsum("...d,dhk->...hk", x, params["wq"].astype(cfg.cdtype))
    k = jnp.einsum("...d,dhk->...hk", x, params["wk"].astype(cfg.cdtype))
    v = jnp.einsum("...d,dhk->...hk", x, params["wv"].astype(cfg.cdtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cfg.cdtype)
        k = k + params["bk"].astype(cfg.cdtype)
        v = v + params["bv"].astype(cfg.cdtype)
    if cfg.qk_norm:
        q = apply_norm(params["q_norm"], q, cfg)
        k = apply_norm(params["k_norm"], k, cfg)
    if cfg.use_rope:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, theta, cfg.mrope_sections)
        else:
            pos = positions if positions.ndim <= 2 else positions[0]
            q = apply_rope(q, pos, theta)
            k = apply_rope(k, pos, theta)
    return q, k, v


def _pos1d(positions):
    return positions if positions.ndim <= 2 else positions[0]


def attn_forward(params, x, cfg: ModelConfig, positions, *, window: int = 0,
                 theta: Optional[float] = None):
    """Full-sequence attention (training / prefill compute)."""
    theta = theta or cfg.rope_theta
    q, k, v = _project_qkv(params, x, cfg, positions, theta)
    pos = _pos1d(positions)
    out = attention_core(q, k, v, pos, pos, cfg, causal=cfg.causal,
                         window=window, softcap=cfg.attn_logit_softcap)
    return jnp.einsum("...hk,hkd->...d", out, params["wo"].astype(cfg.cdtype))


def init_kv_cache(cfg: ModelConfig, batch: int, s_cache: int, window: int = 0,
                  dtype=None):
    size = min(window, s_cache) if window else s_cache
    dtype = dtype or cfg.cdtype
    return {
        "k": jnp.zeros((batch, size, cfg.nkv, cfg.hd), dtype),
        "v": jnp.zeros((batch, size, cfg.nkv, cfg.hd), dtype),
    }


def attn_prefill(params, x, cfg: ModelConfig, positions, cache, *, window: int = 0,
                 theta: Optional[str] = None):
    """Prefill: full attention + fill the cache with this segment's K/V.

    Cache writes are constrained to the decode layout (sequence on
    `model`) INSIDE the layer scan — otherwise XLA stacks the full
    unsharded cache across layers before resharding once at the end
    (measured: +10 GiB temp on deepseek prefill_32k)."""
    from repro.dist.sharding import constrain
    theta = theta or cfg.rope_theta
    q, k, v = _project_qkv(params, x, cfg, positions, theta)
    k = constrain(k, "B", "M", None, None)
    v = constrain(v, "B", "M", None, None)
    pos = _pos1d(positions)
    out = attention_core(q, k, v, pos, pos, cfg, causal=cfg.causal,
                         window=window, softcap=cfg.attn_logit_softcap)
    size = cache["k"].shape[1]
    S = k.shape[1]
    if S >= size:
        # keep the trailing window, laid out so position p sits at slot p % size
        kw, vw = k[:, S - size:], v[:, S - size:]
        shift = S % size
        kw = jnp.roll(kw, shift, axis=1)
        vw = jnp.roll(vw, shift, axis=1)
        cache = {"k": kw.astype(cache["k"].dtype), "v": vw.astype(cache["v"].dtype)}
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    y = jnp.einsum("...hk,hkd->...d", out, params["wo"].astype(cfg.cdtype))
    return y, cache


def attn_decode(params, x, cfg: ModelConfig, positions, cache, index, *,
                window: int = 0, theta: Optional[float] = None):
    """One-token decode. ``index`` = number of tokens already in the cache.

    x: (B, 1, d); positions: (B, 1) or (3, B, 1) for M-RoPE.
    """
    theta = theta or cfg.rope_theta
    q, k, v = _project_qkv(params, x, cfg, positions, theta)
    size = cache["k"].shape[1]
    slot = (index % size) if window else jnp.minimum(index, size - 1)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, slot, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, slot, 0, 0)),
    }
    B = x.shape[0]
    q_pos = _pos1d(positions)
    if window:
        # ring buffer: cache slot s holds absolute position derived from index
        base = index - size
        kv_pos = jnp.arange(size)[None, :] + 0 * q_pos[..., :1]
        abs_pos = jnp.where(jnp.arange(size)[None, :] <= slot,
                            jnp.arange(size)[None, :] + (index // size) * size,
                            jnp.arange(size)[None, :] + (index // size - 1) * size)
        kv_pos = abs_pos
        valid = None
        out = attention_core(q, cache["k"].astype(cfg.cdtype),
                             cache["v"].astype(cfg.cdtype), q_pos, kv_pos, cfg,
                             causal=True, window=window,
                             softcap=cfg.attn_logit_softcap)
    else:
        kv_pos = jnp.broadcast_to(jnp.arange(size)[None, :], (B, size))
        out = attention_core(q, cache["k"].astype(cfg.cdtype),
                             cache["v"].astype(cfg.cdtype), q_pos, kv_pos, cfg,
                             causal=True, window=0,
                             softcap=cfg.attn_logit_softcap,
                             kv_len_valid=index + 1)
    y = jnp.einsum("...hk,hkd->...d", out, params["wo"].astype(cfg.cdtype))
    return y, cache


# ========================================================================= MLA
def init_mla(key, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(key, 8)
    d, H = cfg.d_model, cfg.nq
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {
        "w_dq": dense_init(ks[0], d, cfg.q_lora_rank, cfg.pdtype),
        "q_norm": init_norm(cfg, cfg.q_lora_rank),
        "w_uq": dense_init(ks[1], cfg.q_lora_rank, (H, qk), cfg.pdtype),
        "w_dkv": dense_init(ks[2], d, cfg.kv_lora_rank, cfg.pdtype),
        "kv_norm": init_norm(cfg, cfg.kv_lora_rank),
        "w_kr": dense_init(ks[3], d, cfg.qk_rope_head_dim, cfg.pdtype),
        "w_uk": dense_init(ks[4], cfg.kv_lora_rank, (H, cfg.qk_nope_head_dim), cfg.pdtype),
        "w_uv": dense_init(ks[5], cfg.kv_lora_rank, (H, cfg.v_head_dim), cfg.pdtype),
        "wo": dense_init(ks[6], H * cfg.v_head_dim, d, cfg.pdtype).reshape(
            H, cfg.v_head_dim, d),
    }
    return p


def _mla_q(params, x, cfg: ModelConfig, positions):
    cq = apply_norm(params["q_norm"],
                    jnp.einsum("...d,dr->...r", x, params["w_dq"].astype(cfg.cdtype)), cfg)
    q = jnp.einsum("...r,rhk->...hk", cq, params["w_uq"].astype(cfg.cdtype))
    qn, qr = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    qr = apply_rope(qr, _pos1d(positions), cfg.rope_theta)
    return qn, qr


def _mla_latent(params, x, cfg: ModelConfig, positions):
    ckv = apply_norm(params["kv_norm"],
                     jnp.einsum("...d,dr->...r", x, params["w_dkv"].astype(cfg.cdtype)), cfg)
    kr = jnp.einsum("...d,dk->...k", x, params["w_kr"].astype(cfg.cdtype))
    kr = apply_rope(kr[..., None, :], _pos1d(positions), cfg.rope_theta)[..., 0, :]
    return ckv, kr


def mla_forward(params, x, cfg: ModelConfig, positions):
    """Training / prefill-compute MLA: expand K/V and run standard attention."""
    qn, qr = _mla_q(params, x, cfg, positions)
    ckv, kr = _mla_latent(params, x, cfg, positions)
    kn = jnp.einsum("...r,rhk->...hk", ckv, params["w_uk"].astype(cfg.cdtype))
    v = jnp.einsum("...r,rhk->...hk", ckv, params["w_uv"].astype(cfg.cdtype))
    q = jnp.concatenate([qn, qr], axis=-1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[..., None, :], kn.shape[:-1] + (cfg.qk_rope_head_dim,))], axis=-1)
    pos = _pos1d(positions)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    out = attention_core(q, k, v, pos, pos, cfg, causal=True, scale=scale)
    return jnp.einsum("...hk,hkd->...d", out, params["wo"].astype(cfg.cdtype))


def mla_latent_chunked(qn, qr, ckv, kr, w_uk, w_uv, wo, cfg: ModelConfig,
                       chunk: int = 1024):
    """Prefill attention that expands the compressed KV latent CHUNK BY
    CHUNK inside the online-softmax scan — the full (B,S,H,192/128)
    expanded K/V never exists (multi-GB at 32k x 128 heads; measured as
    the dominant prefill transient). Forward-only: prefill has no backward,
    so there is no residual-size penalty. This is the jnp statement of the
    MLA-native flash kernel (expansion happens in VMEM on TPU).
    """
    B, Sq, H, Dn = qn.shape
    Dr = qr.shape[-1]
    R = ckv.shape[-1]
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    S = ckv.shape[1]
    chunk = min(chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        kr = jnp.pad(kr, ((0, 0), (0, pad), (0, 0)))
    ckv_c = jnp.moveaxis(ckv.reshape(B, n, chunk, R), 1, 0)
    kr_c = jnp.moveaxis(kr.reshape(B, n, chunk, Dr), 1, 0)
    q_pos = jnp.arange(Sq)[None]
    qnf = qn.astype(jnp.float32) * scale
    qrf = qr.astype(jnp.float32) * scale
    Dv = cfg.v_head_dim

    @jax.named_scope("pallas_flash_attention")
    def body(carry, xs):
        m, l, acc = carry
        ckv_i, kr_i, ci = xs
        kn_i = jnp.einsum("bkr,rhd->bkhd", ckv_i.astype(jnp.float32),
                          w_uk.astype(jnp.float32))
        v_i = jnp.einsum("bkr,rhd->bkhd", ckv_i.astype(jnp.float32),
                         w_uv.astype(jnp.float32))
        logits = (jnp.einsum("bqhd,bkhd->bhqk", qnf, kn_i)
                  + jnp.einsum("bqhd,bkd->bhqk", qrf,
                               kr_i.astype(jnp.float32)))
        kv_pos = ci * chunk + jnp.arange(chunk)[None]
        bias = _mask_bias(q_pos, kv_pos, True, 0, jnp.asarray(S))
        logits = logits + bias[:, None]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_i)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (ckv_c, kr_c, jnp.arange(n)))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(cfg.cdtype)
    out = jnp.moveaxis(out, 1, 2)                         # (B,Sq,H,Dv)
    return jnp.einsum("...hk,hkd->...d", out, wo.astype(cfg.cdtype))


def init_mla_cache(cfg: ModelConfig, batch: int, s_cache: int, dtype=None):
    dtype = dtype or cfg.cdtype
    return {
        "ckv": jnp.zeros((batch, s_cache, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, s_cache, cfg.qk_rope_head_dim), dtype),
    }


def mla_prefill(params, x, cfg: ModelConfig, positions, cache):
    # latent-chunked attention: never materializes the expanded K/V
    # (EXPERIMENTS §Perf cell C, prefill iteration)
    from repro.dist.sharding import constrain
    qn, qr = _mla_q(params, x, cfg, positions)
    ckv, kr = _mla_latent(params, x, cfg, positions)
    ckv = constrain(ckv, "B", "M", None)
    kr = constrain(kr, "B", "M", None)
    y = mla_latent_chunked(qn, qr, ckv, kr, params["w_uk"], params["w_uv"],
                           params["wo"], cfg, chunk=cfg.attn_chunk)
    cache = {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "kr": jax.lax.dynamic_update_slice(cache["kr"], kr.astype(cache["kr"].dtype), (0, 0, 0)),
    }
    return y, cache


def mla_decode(params, x, cfg: ModelConfig, positions, cache, index):
    """Absorbed-weight MLA decode: score & combine in the 512-d latent space.

    This is the deployment-mode trick from the paper's citation
    [arXiv:2405.04434 §2.1]: fold W_uk into the query and W_uv after the
    latent-space combine, so per-step work is O(S · kv_lora) instead of
    O(S · H · head_dim) and the cache stays compressed.
    """
    qn, qr = _mla_q(params, x, cfg, positions)          # (B,1,H,nope),(B,1,H,rope)
    ckv_t, kr_t = _mla_latent(params, x, cfg, positions)
    cache = {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv_t.astype(cache["ckv"].dtype), (0, index, 0)),
        "kr": jax.lax.dynamic_update_slice(cache["kr"], kr_t.astype(cache["kr"].dtype), (0, index, 0)),
    }
    ckv = cache["ckv"].astype(jnp.float32)
    kr = cache["kr"].astype(jnp.float32)
    # absorb W_uk into q
    q_lat = jnp.einsum("bqhn,rhn->bqhr", qn.astype(jnp.float32),
                       params["w_uk"].astype(jnp.float32))      # (B,1,H,R)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    logits = (jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv) +
              jnp.einsum("bqhk,bsk->bhqs", qr.astype(jnp.float32), kr)) * scale
    S = ckv.shape[1]
    valid = (jnp.arange(S)[None, None, None, :] <= index)
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv)          # (B,1,H,R)
    v = jnp.einsum("bqhr,rhk->bqhk", ctx_lat, params["w_uv"].astype(jnp.float32))
    y = jnp.einsum("bqhk,hkd->bqd", v.astype(cfg.cdtype),
                   params["wo"].astype(cfg.cdtype))
    return y, cache
