"""Attention variants: GQA (train/prefill/decode), MLA (DeepSeek-style with
latent KV compression + decode-time weight absorption), and cross-attention
(whisper).  All projections are quantization-aware (models/layers.qlinear).

Full-sequence attention uses a *blockwise online-softmax* formulation
(lax.scan over KV chunks) so the S×S score matrix never materializes — this
is what makes the 32k-prefill dry-run cells fit in HBM, and it is the compute
pattern a Pallas flash kernel would implement on real hardware (the jnp
version is the oracle; see kernels/).

KV caches default to int8 with per-token scales (layer-wise activation
quantization applied to the cache — the paper's activation scheme, DESIGN.md
§2).  A ``kv_spec`` (models/kv_quant.KVQuantSpec) switches a ring to the
**channel-wise packed** layout: contiguous feature-axis channel groups at
2/4/8 bits, one scale per token per group, stored packed in uint8; decode
then either dequantizes with the jnp reference or — ``backend="pallas"`` —
runs the fused decode-attention kernel that unpacks+scales ring tiles in
VMEM right before the dot (kernels/decode_attention.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.cache import paged
from repro.dist import sharding as shd
from repro.dist.sharding import constrain
from repro.api.policy import PrecisionPolicy
from repro.kernels import decode_attention as datt_kernel
from repro.models import kv_quant as kvq
from repro.models import layers as L


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------

def init_gqa(key, cfg, dtype) -> tuple[dict, dict]:
    """Returns (params, nas) for one GQA attention block."""
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    params = {
        "wq": L.linear_init(ks[0], d, H * hd, dtype, bias=cfg.qkv_bias),
        "wk": L.linear_init(ks[1], d, KV * hd, dtype, bias=cfg.qkv_bias),
        "wv": L.linear_init(ks[2], d, KV * hd, dtype, bias=cfg.qkv_bias),
        "wo": L.linear_init(ks[3], H * hd, d, dtype),
    }
    nas = {name: L.nas_init(ks[i], p["w"].shape[0], cfg.quant)
           for i, (name, p) in enumerate(params.items())}
    return params, nas


def init_mla(key, cfg, dtype) -> tuple[dict, dict]:
    """DeepSeek-V3 Multi-head Latent Attention parameters."""
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    params = {
        "wq_a": L.linear_init(ks[0], d, qr, dtype),
        "wq_b": L.linear_init(ks[1], qr, H * (nope + rope), dtype),
        "wkv_a": L.linear_init(ks[2], d, kvr + rope, dtype),
        "wkv_b": L.linear_init(ks[3], kvr, H * (nope + vd), dtype),
        "wo": L.linear_init(ks[4], H * vd, d, dtype),
        "q_norm": L.norm_init(qr, "rmsnorm", dtype),
        "kv_norm": L.norm_init(kvr, "rmsnorm", dtype),
    }
    nas = {name: L.nas_init(ks[min(i, 5)], params[name]["w"].shape[0], cfg.quant)
           for i, name in enumerate(("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))}
    return params, nas


# ---------------------------------------------------------------------------
# Blockwise (online-softmax) attention core
# ---------------------------------------------------------------------------

def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool, k_chunk: int = 1024,
                        q_offset: int = 0) -> jnp.ndarray:
    """softmax(q kᵀ / sqrt(d)) v without materializing the S_q×S_kv matrix.

    q: (B, H, Sq, D); k/v: (B, H, Skv, D) (GQA callers pre-broadcast KV heads
    by reshaping into (B, KV, rep, ...) groups — see gqa_core).
    Scans over KV chunks maintaining running (max, denom, numerator).
    """
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]                 # MLA: value head dim may differ from qk
    Skv = k.shape[2]
    k_chunk = min(k_chunk, Skv)
    n_chunks = math.ceil(Skv / k_chunk)
    pad = n_chunks * k_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q = constrain(q, "D", "M", None, None)
    kc = k.reshape(B, H, n_chunks, k_chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, H, n_chunks, k_chunk, Dv).transpose(2, 0, 1, 3, 4)
    kc = constrain(kc, None, "D", "M", None, None)
    vc = constrain(vc, None, "D", "M", None, None)
    scale = 1.0 / math.sqrt(D)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, xs):
        m, d_sum, acc = carry
        kb, vb, ci = xs
        kb = constrain(kb, "D", "M", None, None)
        vb = constrain(vb, "D", "M", None, None)
        kv_pos = ci * k_chunk + jnp.arange(k_chunk)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kb).astype(jnp.float32) * scale
        mask = kv_pos[None, :] < Skv  # padding mask
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        else:
            mask = jnp.broadcast_to(mask, (Sq, k_chunk))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard -inf rows (fully masked chunk): exp(-inf - -inf) -> use safe max
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        d_new = d_sum * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, d_new, acc_new), None

    m0 = constrain(jnp.full((B, H, Sq), -jnp.inf, jnp.float32),
                   "D", "M", None)
    d0 = constrain(jnp.zeros((B, H, Sq), jnp.float32), "D", "M", None)
    a0 = constrain(jnp.zeros((B, H, Sq, Dv), jnp.float32),
                   "D", "M", None, None)
    (m, d_sum, acc), _ = jax.lax.scan(
        body, (m0, d0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(d_sum, 1e-30)[..., None]
    return out.astype(q.dtype)


def gqa_core(q, k, v, n_heads: int, n_kv: int, causal: bool,
             q_offset: int = 0, k_chunk: int = 1024) -> jnp.ndarray:
    """Grouped-query attention: q (B,S,H,D), k/v (B,S,KV,D) -> (B,S,H,D)."""
    # serving under a mesh: attention math runs replicated (the f32 softmax
    # reduction order must not depend on the partitioning) — identity on a
    # single device and during training
    q, k, v = (shd.replicate_serving(t) for t in (q, k, v))
    B, Sq, H, D = q.shape
    Dv = v.shape[-1]                 # MLA: value head dim may differ from qk
    rep = n_heads // n_kv
    qh = q.transpose(0, 2, 1, 3)     # (B, H, Sq, D)
    kh = k.transpose(0, 2, 1, 3)     # (B, KV, Skv, D)
    vh = v.transpose(0, 2, 1, 3)
    kh = jnp.repeat(kh, rep, axis=1) if rep > 1 else kh
    vh = jnp.repeat(vh, rep, axis=1) if rep > 1 else vh
    out = blockwise_attention(qh, kh, vh, causal, k_chunk, q_offset)
    return out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# GQA block: train/prefill and cached-decode paths
# ---------------------------------------------------------------------------

def gqa_forward(p: dict, nas: Optional[dict], policy: PrecisionPolicy, cfg,
                x: jnp.ndarray, positions: jnp.ndarray, causal: bool = True,
                k_chunk: int = 1024) -> jnp.ndarray:
    """Full-sequence GQA with RoPE. x: (B, S, d)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.cdtype
    getn = (lambda n: nas[n]) if nas is not None else (lambda n: None)
    q = L.qlinear(x, p["wq"], getn("wq"), policy, cfg.quant, compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
    k = L.qlinear(x, p["wk"], getn("wk"), policy, cfg.quant, compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
    v = L.qlinear(x, p["wv"], getn("wv"), policy, cfg.quant, compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
    q = constrain(q.reshape(B, S, H, hd), "D", None, "M", None)
    k = constrain(k.reshape(B, S, KV, hd), "D", None, "M", None)
    v = constrain(v.reshape(B, S, KV, hd), "D", None, "M", None)
    if cfg.rope_partial > 0:
        cos, sin, rot = L.rope_freqs(hd, cfg.rope_theta, positions,
                                     cfg.rope_partial)
        q = L.apply_rope(q, cos, sin, rot)
        k = L.apply_rope(k, cos, sin, rot)
    o = gqa_core(q, k, v, H, KV, causal, k_chunk=k_chunk)
    o = o.reshape(B, S, H * hd)
    return L.qlinear(o, p["wo"], getn("wo"), policy, cfg.quant,
                     compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))


def init_gqa_cache(cfg, batch: int, max_len: int,
                   spec: Optional[kvq.KVQuantSpec] = None) -> dict:
    """GQA ring cache.  ``spec=None``: legacy int8 values + per-token scales;
    with a spec the value leaves hold packed sub-byte rows (uint8, feature
    axis in bytes) and the scale leaves one f32 per channel group — same
    keys and tree structure either way, so the paging/merge machinery in
    models/serving.py is layout-agnostic."""
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    if spec is None:
        return {
            "k": jnp.zeros((batch, KV, max_len, hd), jnp.int8),
            "v": jnp.zeros((batch, KV, max_len, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, KV, max_len, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, KV, max_len, 1), jnp.float32),
        }
    assert spec.feat == hd, (spec, hd)
    nb, G = spec.packed_bytes, spec.n_groups
    return {
        "k": jnp.zeros((batch, KV, max_len, nb), jnp.uint8),
        "v": jnp.zeros((batch, KV, max_len, nb), jnp.uint8),
        "k_scale": jnp.zeros((batch, KV, max_len, G), jnp.float32),
        "v_scale": jnp.zeros((batch, KV, max_len, G), jnp.float32),
    }


def quant_per_token(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token symmetric int8 quantization of KV-cache entries.

    ``t (..., D) -> (q int8 (..., D), scale f32 (..., 1))`` with
    ``t ≈ q * scale``; one amax over the feature axis per leading index —
    the paper's layer-wise activation scheme applied per cached token.
    The single quantizer behind every cache write (GQA K/V, the MLA
    latent, and the prefill cache builders in models/serving.py); public
    as of PR 4 so serving does not reach into a private helper.
    """
    amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
    scale = jnp.maximum(amax.astype(jnp.float32), 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def slot_write_pos(pos: jnp.ndarray, live: Optional[jnp.ndarray],
                   max_len: int) -> jnp.ndarray:
    """Per-slot ring-write index: dead slots write out of bounds.

    The serving cache writers scatter each row's new entry at its own
    position; with ``mode="drop"`` an out-of-bounds index silently skips
    the row, so a freed slot (``live=False``) leaves its pooled cache
    untouched while the live slots in the same fixed-width batch advance.
    """
    pos = pos.astype(jnp.int32)
    return pos if live is None else jnp.where(live, pos, max_len)


@jax.named_scope("attn")
def gqa_decode(p: dict, cfg, x: jnp.ndarray, cache: dict,
               pos: jnp.ndarray, dq_linear,
               live: Optional[jnp.ndarray] = None,
               pages: Optional[jnp.ndarray] = None,
               page_size: Optional[int] = None,
               kv_spec: Optional[kvq.KVQuantSpec] = None,
               backend: str = "jnp") -> tuple[jnp.ndarray, dict]:
    """One-token decode with quantized KV cache, per-slot positions.

    ``x``: (B, 1, d); ``pos``: (B,) int32 **position vector** — row ``b``
    writes its new KV at ring index ``pos[b]`` and attends to history
    ``<= pos[b]``, so independently-progressed requests decode in one
    fixed-width batch (continuous batching); ``live``: optional (B,) bool —
    rows with ``live=False`` drop their ring write (freed slots stay
    untouched).  ``dq_linear`` is the linear application function for the
    deployed weight format (see models/serving.py) — this function is
    format-agnostic.

    ``pages``: optional (B, P) int32 page table for the **paged** cache
    (repro/cache): the cache leaves then hold physical pages ``(num_pages,
    KV, page_size, hd)`` instead of per-slot rings; row ``b``'s write
    scatters into page ``pages[b, pos[b] // page_size]`` and attention
    gathers its ring view through the table.  The gathered view is exactly
    the dense ``(B, KV, P*page_size, hd)`` ring, so the attention math —
    and its bits — are identical to the dense path.

    ``kv_spec``: optional channel-wise packed cache layout (the cache leaves
    must come from ``init_gqa_cache(..., spec=kv_spec)``).  New tokens
    quantize per channel group and the ring stays packed through the
    scatter/gather (packing is feature-axis only, so page boundaries never
    split a byte); ``backend="pallas"`` then attends through the fused
    decode-attention kernel (in-VMEM unpack+scale), anything else through
    the jnp dequant reference — token-identical paths, pinned by
    tests/test_kv_quant.py.
    """
    if x.shape[1] > 1:                # speculative verify: W tokens at once
        return _gqa_decode_multi(p, cfg, x, cache, pos, dq_linear, live,
                                 pages, page_size, kv_spec, backend)
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.cdtype
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:                 # legacy scalar: all slots synchronized
        pos = jnp.broadcast_to(pos[None], (B,))
    q = dq_linear(x, p["wq"]).reshape(B, 1, H, hd)
    k = dq_linear(x, p["wk"]).reshape(B, 1, KV, hd)
    v = dq_linear(x, p["wv"]).reshape(B, 1, KV, hd)
    if cfg.rope_partial > 0:
        cos, sin, rot = L.rope_freqs(hd, cfg.rope_theta,
                                     pos[:, None], cfg.rope_partial)
        q = L.apply_rope(q, cos, sin, rot)
        k = L.apply_rope(k, cos, sin, rot)
    # mesh serving: attention operands replicate (identity off-mesh)
    q, k, v = (shd.replicate_serving(t) for t in (q, k, v))
    # append new kv (int8 per-token or packed channel-wise), one ring
    # index per slot
    with jax.named_scope("kv_write"):
        if kv_spec is None:
            kq, ks = quant_per_token(k.transpose(0, 2, 1, 3))  # (B, KV, 1, hd)
            vq, vs = quant_per_token(v.transpose(0, 2, 1, 3))
        else:
            kq, ks = kvq.quant_channelwise(k.transpose(0, 2, 1, 3), kv_spec)
            vq, vs = kvq.quant_channelwise(v.transpose(0, 2, 1, 3), kv_spec)
        if pages is None:
            S = cache["k"].shape[2]
            bidx = jnp.arange(B)
            wpos = slot_write_pos(pos, live, S)
            cache = {
                "k": cache["k"].at[bidx, :, wpos].set(kq[:, :, 0],
                                                      mode="drop"),
                "v": cache["v"].at[bidx, :, wpos].set(vq[:, :, 0],
                                                      mode="drop"),
                "k_scale": cache["k_scale"].at[bidx, :, wpos].set(ks[:, :, 0],
                                                                  mode="drop"),
                "v_scale": cache["v_scale"].at[bidx, :, wpos].set(vs[:, :, 0],
                                                                  mode="drop"),
            }
        else:
            NP = cache["k"].shape[0]
            S = pages.shape[1] * page_size
            phys, off = paged.write_coords(pos, live, pages, page_size, NP)
            cache = {
                "k": cache["k"].at[phys, :, off].set(kq[:, :, 0], mode="drop"),
                "v": cache["v"].at[phys, :, off].set(vq[:, :, 0], mode="drop"),
                "k_scale": cache["k_scale"].at[phys, :, off].set(ks[:, :, 0],
                                                                 mode="drop"),
                "v_scale": cache["v_scale"].at[phys, :, off].set(vs[:, :, 0],
                                                                 mode="drop"),
            }
    if pages is None:
        ki, vi, ksc, vsc = (cache["k"], cache["v"],
                            cache["k_scale"], cache["v_scale"])
    else:
        ki = paged.gather_pages(cache["k"], pages)       # (B, KV, S, hd)
        vi = paged.gather_pages(cache["v"], pages)
        ksc = paged.gather_pages(cache["k_scale"], pages)
        vsc = paged.gather_pages(cache["v_scale"], pages)
    ki, vi, ksc, vsc = (shd.replicate_serving(t)
                        for t in (ki, vi, ksc, vsc))
    rep = H // KV
    if kv_spec is not None and backend == "pallas":
        # fused path: the ring stays packed into VMEM; unpack+scale happens
        # per (slot, kv-head) tile right before the dot
        # q keeps its native dtype (f32 after RoPE): the kernel's score dot
        # then promotes exactly like the reference einsum, so fused and jnp
        # paths stay token-identical
        qg = q.transpose(0, 2, 1, 3).reshape(B, KV, rep, hd)
        o = shd.replicated_kernel(
            lambda *a: datt_kernel.decode_attention(
                *a, kv_spec.bits, kv_spec.sizes, out_dtype=cd))(
            qg, ki, ksc, vi, vsc, pos)
        return dq_linear(o.reshape(B, 1, H * hd), p["wo"]), cache
    if kv_spec is None:
        kf = (ki.astype(jnp.float32) * ksc).astype(cd)
        vf = (vi.astype(jnp.float32) * vsc).astype(cd)
    else:
        kf = kvq.dequant_channelwise(ki, ksc, kv_spec, cd)
        vf = kvq.dequant_channelwise(vi, vsc, kv_spec, cd)
    qh = q.transpose(0, 2, 1, 3)                          # (B, H, 1, hd)
    # grouped score: expand kv heads to full head count
    kfe = jnp.repeat(kf, rep, axis=1) if rep > 1 else kf  # (B, H, S, hd)
    vfe = jnp.repeat(vf, rep, axis=1) if rep > 1 else vf
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kfe).astype(jnp.float32)
    s = s / math.sqrt(hd)
    valid = jnp.arange(S)[None, None, None, :] <= pos[:, None, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, vfe)
    o = o.transpose(0, 2, 1, 3).reshape(B, 1, H * hd)
    return dq_linear(o, p["wo"]), cache


def _gqa_decode_multi(p: dict, cfg, x: jnp.ndarray, cache: dict,
                      pos: jnp.ndarray, dq_linear,
                      live: Optional[jnp.ndarray] = None,
                      pages: Optional[jnp.ndarray] = None,
                      page_size: Optional[int] = None,
                      kv_spec: Optional[kvq.KVQuantSpec] = None,
                      backend: str = "jnp") -> tuple[jnp.ndarray, dict]:
    """W-token verify decode: one batched KV scatter, then W attention steps.

    ``x (B, W, d)`` are the speculative verify inputs ``[t0, d1..d_{W-1}]``;
    row ``b``'s token ``j`` lives at ring position ``pos[b] + j``, so ALL W
    entries are written in one scatter up front.  That is the cache-rewind
    contract: entries past the eventually-accepted length are never
    unwound — the ``<= pos`` attention mask keeps them invisible until a
    later write overwrites them (exactly like stale reused pages, pinned by
    tests/test_paged_cache.py).  Attention then runs as W successive
    single-query steps whose operands match the baseline :func:`gqa_decode`
    step for step — same masks, same per-step fused-kernel calls — so the
    verify logits are bit-identical to W sequential decode launches (the
    greedy parity anchor of tests/test_speculative.py).
    """
    B, W, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.cdtype
    pos = jnp.asarray(pos, jnp.int32)
    posk = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)
    q = dq_linear(x, p["wq"]).reshape(B, W, H, hd)
    k = dq_linear(x, p["wk"]).reshape(B, W, KV, hd)
    v = dq_linear(x, p["wv"]).reshape(B, W, KV, hd)
    if cfg.rope_partial > 0:
        cos, sin, rot = L.rope_freqs(hd, cfg.rope_theta, posk,
                                     cfg.rope_partial)
        q = L.apply_rope(q, cos, sin, rot)
        k = L.apply_rope(k, cos, sin, rot)
    # mesh serving: attention operands replicate (identity off-mesh)
    q, k, v = (shd.replicate_serving(t) for t in (q, k, v))
    with jax.named_scope("kv_write"):
        if kv_spec is None:
            kq, ks = quant_per_token(k.transpose(0, 2, 1, 3))  # (B,KV,W,.)
            vq, vs = quant_per_token(v.transpose(0, 2, 1, 3))
        else:
            kq, ks = kvq.quant_channelwise(k.transpose(0, 2, 1, 3), kv_spec)
            vq, vs = kvq.quant_channelwise(v.transpose(0, 2, 1, 3), kv_spec)
        # (B, W, KV, .)
        kq, ks = kq.transpose(0, 2, 1, 3), ks.transpose(0, 2, 1, 3)
        vq, vs = vq.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3)
        if pages is None:
            S = cache["k"].shape[2]
            bidx = jnp.arange(B)[:, None]                            # (B, 1)
            wposk = posk if live is None else jnp.where(live[:, None], posk, S)
            # advanced indices (bidx, wposk) separated by the KV-head slice ->
            # their broadcast (B, W) dims lead, so values are (B, W, KV, feat)
            cache = {
                "k": cache["k"].at[bidx, :, wposk].set(kq, mode="drop"),
                "v": cache["v"].at[bidx, :, wposk].set(vq, mode="drop"),
                "k_scale": cache["k_scale"].at[bidx, :, wposk].set(
                    ks, mode="drop"),
                "v_scale": cache["v_scale"].at[bidx, :, wposk].set(
                    vs, mode="drop"),
            }
        else:
            NP = cache["k"].shape[0]
            S = pages.shape[1] * page_size
            phys, off = paged.write_coords(posk, live, pages, page_size, NP)
            cache = {
                "k": cache["k"].at[phys, :, off].set(kq, mode="drop"),
                "v": cache["v"].at[phys, :, off].set(vq, mode="drop"),
                "k_scale": cache["k_scale"].at[phys, :, off].set(ks,
                                                                 mode="drop"),
                "v_scale": cache["v_scale"].at[phys, :, off].set(vs,
                                                                 mode="drop"),
            }
    if pages is None:
        ki, vi, ksc, vsc = (cache["k"], cache["v"],
                            cache["k_scale"], cache["v_scale"])
    else:
        ki = paged.gather_pages(cache["k"], pages)       # (B, KV, S, hd)
        vi = paged.gather_pages(cache["v"], pages)
        ksc = paged.gather_pages(cache["k_scale"], pages)
        vsc = paged.gather_pages(cache["v_scale"], pages)
    ki, vi, ksc, vsc = (shd.replicate_serving(t)
                        for t in (ki, vi, ksc, vsc))
    rep = H // KV
    outs = []
    if kv_spec is not None and backend == "pallas":
        for j in range(W):
            qg = q[:, j:j + 1].transpose(0, 2, 1, 3).reshape(B, KV, rep, hd)
            o = shd.replicated_kernel(
                lambda *a: datt_kernel.decode_attention(
                    *a, kv_spec.bits, kv_spec.sizes, out_dtype=cd))(
                qg, ki, ksc, vi, vsc, posk[:, j])
            outs.append(o.reshape(B, 1, H * hd))
        return dq_linear(jnp.concatenate(outs, axis=1), p["wo"]), cache
    if kv_spec is None:
        kf = (ki.astype(jnp.float32) * ksc).astype(cd)
        vf = (vi.astype(jnp.float32) * vsc).astype(cd)
    else:
        kf = kvq.dequant_channelwise(ki, ksc, kv_spec, cd)
        vf = kvq.dequant_channelwise(vi, vsc, kv_spec, cd)
    kfe = jnp.repeat(kf, rep, axis=1) if rep > 1 else kf  # (B, H, S, hd)
    vfe = jnp.repeat(vf, rep, axis=1) if rep > 1 else vf
    for j in range(W):
        qh = q[:, j:j + 1].transpose(0, 2, 1, 3)          # (B, H, 1, hd)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kfe).astype(jnp.float32)
        s = s / math.sqrt(hd)
        valid = (jnp.arange(S)[None, None, None, :]
                 <= posk[:, j][:, None, None, None])
        s = jnp.where(valid, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(cd)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, vfe)
        outs.append(o.transpose(0, 2, 1, 3).reshape(B, 1, H * hd))
    return dq_linear(jnp.concatenate(outs, axis=1), p["wo"]), cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): latent KV compression; decode uses weight absorption
# ---------------------------------------------------------------------------

def mla_forward(p: dict, nas: Optional[dict], policy: PrecisionPolicy, cfg,
                x: jnp.ndarray, positions: jnp.ndarray,
                k_chunk: int = 1024) -> jnp.ndarray:
    """Full-sequence MLA (train/prefill): expand latents to per-head k/v."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cd = cfg.cdtype
    getn = (lambda n: nas[n]) if nas is not None else (lambda n: None)

    cq = L.qlinear(x, p["wq_a"], getn("wq_a"), policy, cfg.quant,
                   compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
    cq = L.rmsnorm(cq, p["q_norm"])
    q = L.qlinear(cq, p["wq_b"], getn("wq_b"), policy, cfg.quant,
                  compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg)).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv = L.qlinear(x, p["wkv_a"], getn("wkv_a"), policy, cfg.quant,
                    compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
    c_kv, k_rope = ckv[..., :kvr], ckv[..., kvr:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"])
    kv = L.qlinear(c_kv, p["wkv_b"], getn("wkv_b"), policy, cfg.quant,
                   compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg)).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    cos, sin, rot = L.rope_freqs(rope, cfg.rope_theta, positions, 1.0)
    q_rope = L.apply_rope(q_rope, cos, sin, rot)
    k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin, rot)  # shared head
    k_rope = jnp.broadcast_to(k_rope, (B, S, H, rope))

    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope], axis=-1)
    o = gqa_core(q_full, k_full, v, H, H, causal=True, k_chunk=k_chunk)
    o = o.reshape(B, S, H * vd)
    return L.qlinear(o, p["wo"], getn("wo"), policy, cfg.quant,
                     compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))


def init_mla_cache(cfg, batch: int, max_len: int,
                   spec: Optional[kvq.KVQuantSpec] = None) -> dict:
    """MLA cache stores the *latent* c_kv + shared k_rope — (kvr + rope) per
    token instead of 2*H*hd: the paper-aligned memory win for decode.

    With a ``spec`` the latent leaf holds packed channel-wise sub-byte rows
    (``kv_lora_rank`` is the feature axis) and the scale leaf one f32 per
    channel group; ``krope`` stays bf16 — it is the shared rotary phase
    (``qk_rope_dim`` small), not a searched activation.
    """
    if spec is None:
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), jnp.int8),
            "ckv_scale": jnp.zeros((batch, max_len, 1), jnp.float32),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim),
                               jnp.bfloat16),
        }
    assert spec.feat == cfg.kv_lora_rank, (spec, cfg.kv_lora_rank)
    return {
        "ckv": jnp.zeros((batch, max_len, spec.packed_bytes), jnp.uint8),
        "ckv_scale": jnp.zeros((batch, max_len, spec.n_groups), jnp.float32),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), jnp.bfloat16),
    }


@jax.named_scope("attn")
def mla_decode(p: dict, cfg, x: jnp.ndarray, cache: dict, pos: jnp.ndarray,
               dq_linear, live: Optional[jnp.ndarray] = None,
               pages: Optional[jnp.ndarray] = None,
               page_size: Optional[int] = None,
               kv_spec: Optional[kvq.KVQuantSpec] = None
               ) -> tuple[jnp.ndarray, dict]:
    """One-token MLA decode, fully packed, per-slot positions.

    ``pos`` is a (B,) int32 position vector (see :func:`gqa_decode`): each
    row writes its latent at its own ring index and attends to its own
    history; ``live=False`` rows drop their write.  ``pages (B, P)`` turns
    the cache leaves into page pools (``(num_pages, page_size, feat)``) and
    routes writes/reads through the table exactly as in :func:`gqa_decode`.

    The pre-PR4 path "absorbed" ``wkv_b`` per head (W_uk / W_uv) from a
    dense ``(c_out, c_in)`` view — re-materializing the full bf16 weight on
    every step, exactly the HBM traffic the searched sub-byte assignment is
    supposed to save.  Decode now expands the cached latents through the
    **packed** ``wkv_b`` matmul instead (``dq_linear`` — the same
    mixed-precision group/fused kernels as prefill) and attends in per-head
    K/V space: mathematically the same attention (absorption is an exact
    linear-algebra rewrite), with every weight read staying sub-byte.  The
    cache layout is unchanged (int8 latent + shared bf16 k_rope), so
    prefill-built caches embed as before.

    Trade-off: expansion re-runs the ``wkv_b`` matmul over all ``S``
    cached latents each step (O(S) activation compute) where absorption
    paid a dense O(1) weight read — the packed win holds while
    ``S * act_bytes`` stays under the dense ``H*(nope+vd)*kvr`` weight
    bytes, i.e. the edge/short-context decode this repo serves.  Packed
    absorption proper needs a transpose (contract-over-``c_out``) packed
    matmul, which the channel-grouped layout does not support — revisit if
    long-context MLA decode becomes a target workload.

    ``kv_spec``: optional channel-wise packed *latent* storage (cache from
    ``init_mla_cache(..., spec=kv_spec)``).  The win is the packed ring
    bytes; the dequantized latent still materializes once per step because
    it immediately expands through the packed ``wkv_b`` matmul — there is
    no attention dot to fuse the latent unpack into (unlike GQA's
    decode-attention kernel), so the channel-wise jnp dequant IS the packed
    path here, on every backend.
    """
    if x.shape[1] > 1:                # speculative verify: W tokens at once
        return _mla_decode_multi(p, cfg, x, cache, pos, dq_linear, live,
                                 pages, page_size, kv_spec)
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cd = cfg.cdtype
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:                 # legacy scalar: all slots synchronized
        pos = jnp.broadcast_to(pos[None], (B,))

    cq = L.rmsnorm(dq_linear(x, p["wq_a"]), p["q_norm"])
    q = dq_linear(cq, p["wq_b"]).reshape(B, 1, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv_new = dq_linear(x, p["wkv_a"])
    c_kv, k_rope_new = ckv_new[..., :kvr], ckv_new[..., kvr:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"])

    cos, sin, rot = L.rope_freqs(rope, cfg.rope_theta, pos[:, None], 1.0)
    q_rope = L.apply_rope(q_rope, cos, sin, rot)
    k_rope_new = L.apply_rope(k_rope_new[:, :, None, :], cos, sin, rot)[:, :, 0]

    with jax.named_scope("kv_write"):
        if kv_spec is None:
            qc, qs = quant_per_token(c_kv)
        else:
            qc, qs = kvq.quant_channelwise(c_kv, kv_spec)
        if pages is None:
            S = cache["ckv"].shape[1]
            bidx = jnp.arange(B)
            wpos = slot_write_pos(pos, live, S)
            cache = {
                "ckv": cache["ckv"].at[bidx, wpos].set(qc[:, 0], mode="drop"),
                "ckv_scale": cache["ckv_scale"].at[bidx, wpos].set(
                    qs[:, 0], mode="drop"),
                "krope": cache["krope"].at[bidx, wpos].set(
                    k_rope_new[:, 0].astype(jnp.bfloat16), mode="drop"),
            }
        else:
            NP = cache["ckv"].shape[0]
            S = pages.shape[1] * page_size
            phys, off = paged.write_coords(pos, live, pages, page_size, NP)
            cache = {
                "ckv": cache["ckv"].at[phys, off].set(qc[:, 0], mode="drop"),
                "ckv_scale": cache["ckv_scale"].at[phys, off].set(qs[:, 0],
                                                                  mode="drop"),
                "krope": cache["krope"].at[phys, off].set(
                    k_rope_new[:, 0].astype(jnp.bfloat16), mode="drop"),
            }
    if pages is None:
        ckv_i, ckv_s, krope_i = (cache["ckv"], cache["ckv_scale"],
                                 cache["krope"])
    else:
        ckv_i = paged.gather_pages(cache["ckv"], pages)      # (B, S, kvr)
        ckv_s = paged.gather_pages(cache["ckv_scale"], pages)
        krope_i = paged.gather_pages(cache["krope"], pages)

    # mesh serving: latent views and queries replicate (identity off-mesh)
    q_nope, q_rope, ckv_i, ckv_s, krope_i = (
        shd.replicate_serving(t)
        for t in (q_nope, q_rope, ckv_i, ckv_s, krope_i))

    # expand latents to per-head K/V through the packed low-rank factor:
    # ckv (B, S, kvr) -> (B, S, H, nope + vd), weights streaming sub-byte
    if kv_spec is None:
        ckv_f = (ckv_i.astype(jnp.float32) * ckv_s).astype(cd)
    else:
        ckv_f = kvq.dequant_channelwise(ckv_i, ckv_s, kv_spec, cd)
    kv = dq_linear(ckv_f, p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    s = jnp.einsum("bqhn,bkhn->bhqk", q_nope.astype(cd),
                   k_nope.astype(cd)).astype(jnp.float32)
    s = s + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(cd),
                       krope_i.astype(cd)).astype(jnp.float32)
    s = s / math.sqrt(nope + rope)
    valid = jnp.arange(S)[None, None, None, :] <= pos[:, None, None, None]
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    o = jnp.einsum("bhqk,bkhv->bqhv", w, v.astype(cd))   # (B, 1, H, vd)
    o = o.reshape(B, 1, H * vd)
    return dq_linear(o, p["wo"]), cache


def _mla_decode_multi(p: dict, cfg, x: jnp.ndarray, cache: dict,
                      pos: jnp.ndarray, dq_linear,
                      live: Optional[jnp.ndarray] = None,
                      pages: Optional[jnp.ndarray] = None,
                      page_size: Optional[int] = None,
                      kv_spec: Optional[kvq.KVQuantSpec] = None
                      ) -> tuple[jnp.ndarray, dict]:
    """W-token MLA verify decode — see :func:`_gqa_decode_multi` for the
    write-then-mask contract.  The W latents land in one batched scatter;
    the packed ``wkv_b`` expansion then runs once over the full ring
    (identical to the baseline step, which also expands all ``S`` cached
    latents), and attention runs W single-query steps under the per-step
    ``<= pos + j`` mask."""
    B, W, _ = x.shape
    H = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cd = cfg.cdtype
    pos = jnp.asarray(pos, jnp.int32)
    posk = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)

    cq = L.rmsnorm(dq_linear(x, p["wq_a"]), p["q_norm"])
    q = dq_linear(cq, p["wq_b"]).reshape(B, W, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv_new = dq_linear(x, p["wkv_a"])
    c_kv, k_rope_new = ckv_new[..., :kvr], ckv_new[..., kvr:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"])

    cos, sin, rot = L.rope_freqs(rope, cfg.rope_theta, posk, 1.0)
    q_rope = L.apply_rope(q_rope, cos, sin, rot)
    k_rope_new = L.apply_rope(k_rope_new[:, :, None, :], cos, sin, rot)[:, :, 0]

    with jax.named_scope("kv_write"):
        if kv_spec is None:
            qc, qs = quant_per_token(c_kv)        # (B, W, kvr) / (B, W, 1)
        else:
            qc, qs = kvq.quant_channelwise(c_kv, kv_spec)
        if pages is None:
            S = cache["ckv"].shape[1]
            bidx = jnp.arange(B)[:, None]                            # (B, 1)
            wposk = posk if live is None else jnp.where(live[:, None], posk, S)
            # adjacent advanced indices (bidx, wposk) broadcast in place ->
            # values are (B, W, feat)
            cache = {
                "ckv": cache["ckv"].at[bidx, wposk].set(qc, mode="drop"),
                "ckv_scale": cache["ckv_scale"].at[bidx, wposk].set(
                    qs, mode="drop"),
                "krope": cache["krope"].at[bidx, wposk].set(
                    k_rope_new.astype(jnp.bfloat16), mode="drop"),
            }
        else:
            NP = cache["ckv"].shape[0]
            S = pages.shape[1] * page_size
            phys, off = paged.write_coords(posk, live, pages, page_size, NP)
            cache = {
                "ckv": cache["ckv"].at[phys, off].set(qc, mode="drop"),
                "ckv_scale": cache["ckv_scale"].at[phys, off].set(qs,
                                                                  mode="drop"),
                "krope": cache["krope"].at[phys, off].set(
                    k_rope_new.astype(jnp.bfloat16), mode="drop"),
            }
    if pages is None:
        ckv_i, ckv_s, krope_i = (cache["ckv"], cache["ckv_scale"],
                                 cache["krope"])
    else:
        ckv_i = paged.gather_pages(cache["ckv"], pages)      # (B, S, kvr)
        ckv_s = paged.gather_pages(cache["ckv_scale"], pages)
        krope_i = paged.gather_pages(cache["krope"], pages)

    # mesh serving: latent views and queries replicate (identity off-mesh)
    q_nope, q_rope, ckv_i, ckv_s, krope_i = (
        shd.replicate_serving(t)
        for t in (q_nope, q_rope, ckv_i, ckv_s, krope_i))

    if kv_spec is None:
        ckv_f = (ckv_i.astype(jnp.float32) * ckv_s).astype(cd)
    else:
        ckv_f = kvq.dequant_channelwise(ckv_i, ckv_s, kv_spec, cd)
    kv = dq_linear(ckv_f, p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]

    outs = []
    for j in range(W):
        s = jnp.einsum("bqhn,bkhn->bhqk", q_nope[:, j:j + 1].astype(cd),
                       k_nope.astype(cd)).astype(jnp.float32)
        s = s + jnp.einsum("bqhr,bkr->bhqk", q_rope[:, j:j + 1].astype(cd),
                           krope_i.astype(cd)).astype(jnp.float32)
        s = s / math.sqrt(nope + rope)
        valid = (jnp.arange(S)[None, None, None, :]
                 <= posk[:, j][:, None, None, None])
        s = jnp.where(valid, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(cd)
        o = jnp.einsum("bhqk,bkhv->bqhv", w, v.astype(cd))   # (B, 1, H, vd)
        outs.append(o.reshape(B, 1, H * vd))
    return dq_linear(jnp.concatenate(outs, axis=1), p["wo"]), cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder): KV from encoder output, not causal.
# ---------------------------------------------------------------------------

def cross_forward(p: dict, nas: Optional[dict], policy: PrecisionPolicy, cfg,
                  x: jnp.ndarray, enc: jnp.ndarray,
                  k_chunk: int = 1024) -> jnp.ndarray:
    B, S, _ = x.shape
    Se = enc.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = cfg.cdtype
    getn = (lambda n: nas[n]) if nas is not None else (lambda n: None)
    q = L.qlinear(x, p["wq"], getn("wq"), policy, cfg.quant,
                  compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg)).reshape(B, S, H, hd)
    k = L.qlinear(enc, p["wk"], getn("wk"), policy, cfg.quant,
                  compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg)).reshape(B, Se, KV, hd)
    v = L.qlinear(enc, p["wv"], getn("wv"), policy, cfg.quant,
                  compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg)).reshape(B, Se, KV, hd)
    o = gqa_core(q, k, v, H, KV, causal=False, k_chunk=k_chunk)
    o = o.reshape(B, S, H * hd)
    return L.qlinear(o, p["wo"], getn("wo"), policy, cfg.quant,
                     compute_dtype=cd,
                  partial_dtype=L.partial_dtype_of(cfg))
