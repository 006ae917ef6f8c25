"""Deployed-model serving: mixed-precision packed weights + int8 KV caches.

This is the paper's Sec. III-C output running as a production inference
path.  Each searched linear becomes up to |P_W| per-precision row groups
(channels reordered offline, group sizes static and 128-aligned — see
core/deploy.py and config.DeploySpec), stored packed in uint8.  At run time
each group is a dense sub-GEMM after an in-register dequant — the TPU
analogue of the paper's "three parallel sub-convolutions", implemented by
kernels/quant_matmul.py (Pallas) with a pure-jnp fallback used on CPU.

Deployed weights move HBM->VMEM as *packed bytes*: a 2-bit channel costs 1/4
the bandwidth of an 8-bit one.  Decode is bandwidth-bound, so the searched
assignment directly scales serving throughput — the paper's memory saving
becomes a latency/energy saving on TPU (DESIGN.md §2).

Formats
-------
A deployed linear is ``{"w": repro.api.QTensor[, "bias": (c_out,)]}`` — the
QTensor (a registered pytree) carries the packed per-precision groups,
per-channel scales and the optional canonical-order restore permutation
(structure-sensitive consumers: attention heads, residual stream).  MoE
expert weights carry a leading E axis on the QTensor's leaves.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.qtensor import QTensor
from repro.cache import paged
from repro.dist import sharding as shd
from repro.core import quantizers as qz
from repro.models import attention as attn
from repro.models import kv_quant as kvq
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# Deployed linear: init (static assignment from DeploySpec) and apply
# ---------------------------------------------------------------------------

def init_deployed_linear(key, c_in: int, c_out: int, cfg,
                         bias: bool = False, expert_axis: int = 0,
                         tile_n="auto") -> dict:
    """Random-weight deployed linear with the config's static group sizes.

    ``expert_axis``: if >0, adds a leading expert dimension E=expert_axis to
    every leaf (MoE).  Weights are synthesized then truly quantized+packed so
    dry-run tensors have exactly the deployed bytes.  Static assignments are
    built group-contiguous, so no permutation is carried.

    ``tile_n`` (default ``"auto"``) additionally builds the tile-aligned
    **fused single-launch layout** — per-expert ragged byte buffers under
    one static tile schedule (the schedule depends only on the static group
    sizes, so all experts share it) — which lets ``backend="pallas"`` serve
    the site as ONE ``pallas_call``, expert-batched for MoE stacks.  Pass
    ``None`` for per-group-only packing.  The builder is traced-safe:
    ``init_deployed_model`` vmaps it over layers, so the schedule is pure
    Python/numpy over static sizes and the byte buffers are jnp ops.
    Contractions beyond the fused kernel's single-K-step budget skip the
    fused layout (per-group fall-back, as in ``QTensor.from_assignment``).

    NOTE this is the traced-safe sibling of
    ``repro.api.qtensor._fused_tile_layout`` (the numpy builder behind
    ``QTensor.from_assignment``): both pack tiles through
    ``kernels/quant_matmul.pack_fused_tiles`` and concatenate them in walk
    order, with zero scales on padding rows and ``fused_perm`` None iff
    padding lands only past ``c_out``.  Here the assignment is
    group-contiguous and ascending-bit, so the walk order is the natural
    group order and no tile sort is needed.
    """
    from repro.api.qtensor import _auto_tile_n
    from repro.kernels import quant_matmul as qmk
    sizes = cfg.deploy.group_sizes(c_out, sorted(cfg.quant.weight_bits))
    E = max(expert_axis, 1)
    if tile_n == "auto":
        # group sizes are align-rounded, so an align-divisible tile keeps
        # the walk order identity (no output gather) for most layers
        tile_n = min(_auto_tile_n(c_out), cfg.deploy.align)
    Kp = -(-c_in // qmk.FUSED_K_ALIGN) * qmk.FUSED_K_ALIGN
    if tile_n is not None and Kp > qmk.K_SINGLE_STEP_MAX:
        tile_n = None                  # contraction too deep to fuse
    packed_groups, scale_groups, used_bits = [], [], []
    fused_p, fused_s, tile_bits, tcol = [], [], [], []
    dep = 0
    for b, n in sizes.items():
        if n == 0:
            continue
        f = qz.pack_factor(b)
        kpad = Kp if tile_n is not None else -(-c_in // f) * f
        kw, ks = jax.random.split(jax.random.fold_in(key, b))
        w = jax.random.normal(kw, (E, n, c_in)) / np.sqrt(c_in)
        alpha = jnp.max(jnp.abs(w), axis=-1, keepdims=True)
        q, scale = qz.quantize_weight_int(w, alpha, b)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, kpad - c_in)))
        packed = qz.pack_int(q, b)                     # (E, n, kpad/f)
        packed_groups.append(packed if expert_axis else packed[0])
        scale_groups.append((scale[..., 0] if expert_axis
                             else scale[0, :, 0]).astype(jnp.float32))
        used_bits.append(b)
        if tile_n is not None:
            pad = (-n) % tile_n
            qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
            sp = jnp.pad(scale[..., 0].astype(jnp.float32),
                         ((0, 0), (0, pad)))
            fused_p.append(qmk.pack_fused_tiles(qp, b, tile_n))
            fused_s.append(sp)
            tile_bits += [b] * ((n + pad) // tile_n)
            tcol += list(range(dep, dep + n)) + [-1] * pad
        dep += n
    fused = {}
    if tile_n is not None:
        fp = jnp.concatenate(fused_p, axis=-1)
        fs = jnp.concatenate(fused_s, axis=-1)
        tcol = np.asarray(tcol)
        if (tcol[:c_out] == np.arange(c_out)).all() and (tcol[c_out:] < 0).all():
            fperm = None               # tile padding only past c_out
        else:
            cols = np.nonzero(tcol >= 0)[0].astype(np.int32)
            gather = np.zeros(c_out, np.int32)
            gather[tcol[cols]] = cols
            fperm = jnp.asarray(gather)
        fused = dict(fused_packed=fp if expert_axis else fp[0],
                     fused_scales=fs if expert_axis else fs[0],
                     fused_perm=fperm, tile_bits=tuple(tile_bits),
                     tile_n=tile_n)
    qt = QTensor(tuple(packed_groups), tuple(scale_groups), None,
                 tuple(used_bits), c_out, c_in,
                 act_bits=cfg.deploy.act_bits, restore_order=False,
                 experts=E if expert_axis else None, **fused)
    out = {"w": qt}
    if bias:
        out["bias"] = jnp.zeros((E, c_out) if expert_axis else (c_out,),
                                jnp.bfloat16)
    return out


def dq_linear(x: jnp.ndarray, dp: dict, compute_dtype=jnp.bfloat16,
              backend: str = "jnp") -> jnp.ndarray:
    """Apply a deployed linear: x (..., c_in) -> (..., c_out).

    Thin wrapper over :meth:`QTensor.matmul` plus the optional bias.
    ``backend="pallas"`` uses the single-launch fused multi-precision
    kernel when the QTensor carries the tile-aligned layout and falls back
    to one unpack+dequant+GEMM launch per precision group otherwise
    (``"pallas-pergroup"`` forces the per-group reference path).

    An expert-stacked QTensor (MoE) maps ``x (E, ..., c_in) -> (E, ...,
    c_out)`` per expert — one expert-batched fused launch under
    ``backend="pallas"``.
    """
    y = dp["w"].matmul(x, compute_dtype, backend)
    if "bias" in dp:
        b = dp["bias"].astype(y.dtype)
        if dp["w"].experts is not None:     # (E, c_out) broadcast over rows
            b = b.reshape((b.shape[0],) + (1,) * (y.ndim - 2) + (b.shape[-1],))
        y = y + b
    return y


def debug_dense_view(dp: dict, compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Dense float view of a deployed linear — DEBUG / ANALYSIS ONLY.

    ``(c_out, c_in)`` for a plain linear, stacked ``(E, c_out, c_in)`` for
    MoE expert weights.  Replaces the removed ``dense_view`` /
    ``dq_expert_weights`` helpers: as of PR 4 **no serving hot path
    dequantizes a full weight** — MoE experts run through the expert-batched
    fused kernel and MLA decode expands its latents through the packed
    ``wkv_b`` matmul (enforced by the all-family monkeypatch guard in
    tests/test_serving_consistency.py).
    """
    return dp["w"].dequantize(compute_dtype)


def deployed_from_search(w, gamma, alpha_w, delta, alpha_x, cfg,
                         restore_order: bool = False) -> dict:
    """Real Sec. III-C transform of a searched linear into deployed format."""
    from repro.core import deploy as dpl
    qt = dpl.deploy_linear(np.asarray(w), np.asarray(gamma),
                           np.asarray(alpha_w),
                           None if delta is None else np.asarray(delta),
                           float(alpha_x), cfg.quant, align=cfg.deploy.align,
                           restore_order=restore_order)
    return {"w": qt}


# ---------------------------------------------------------------------------
# Deployed whole-model init (static assignment — used by the serve dry-run)
# ---------------------------------------------------------------------------

def _dl(key, c_in, c_out, cfg, bias=False):
    return init_deployed_linear(key, c_in, c_out, cfg, bias=bias)


def _init_deployed_attn(key, cfg):
    d, hd = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    if cfg.use_mla:
        qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        return {
            "wq_a": _dl(ks[0], d, qr, cfg),
            "wq_b": _dl(ks[1], qr, H * (nope + rope), cfg),
            "wkv_a": _dl(ks[2], d, kvr + rope, cfg),
            "wkv_b": _dl(ks[3], kvr, H * (nope + vd), cfg),
            "wo": _dl(ks[4], H * vd, d, cfg),
            "q_norm": L.norm_init(qr, "rmsnorm", jnp.bfloat16),
            "kv_norm": L.norm_init(kvr, "rmsnorm", jnp.bfloat16),
        }
    return {
        "wq": _dl(ks[0], d, H * hd, cfg, bias=cfg.qkv_bias),
        "wk": _dl(ks[1], d, KV * hd, cfg, bias=cfg.qkv_bias),
        "wv": _dl(ks[2], d, KV * hd, cfg, bias=cfg.qkv_bias),
        "wo": _dl(ks[3], H * hd, d, cfg),
    }


def _init_deployed_ffn(key, cfg):
    d = cfg.d_model
    # 10 keys: a config with BOTH a shared expert and a dense residual MLP
    # (deepseek + arctic extras combined) must not reuse ks[4..6] for the
    # two sub-trees — they would deploy identical weights (PR 4 bugfix,
    # regression-tested in tests/test_expert_parity.py)
    ks = jax.random.split(key, 10)
    if cfg.n_experts:
        E, ff = cfg.n_experts, cfg.moe_d_ff
        p = {
            "router": (jax.random.normal(ks[0], (E, d)) / np.sqrt(d)
                       ).astype(jnp.bfloat16),
            "we_gate": init_deployed_linear(ks[1], d, ff, cfg, expert_axis=E),
            "we_up": init_deployed_linear(ks[2], d, ff, cfg, expert_axis=E),
            "we_down": init_deployed_linear(ks[3], ff, d, cfg, expert_axis=E),
        }
        if cfg.n_shared_experts:
            sff = ff * cfg.n_shared_experts
            p["shared"] = {"w_gate": _dl(ks[4], d, sff, cfg),
                           "w_up": _dl(ks[5], d, sff, cfg),
                           "w_down": _dl(ks[6], sff, d, cfg)}
        if cfg.dense_residual_ff:
            rff = cfg.dense_residual_ff
            p["dense_res"] = {"w_gate": _dl(ks[7], d, rff, cfg),
                              "w_up": _dl(ks[8], d, rff, cfg),
                              "w_down": _dl(ks[9], rff, d, cfg)}
        return p
    if cfg.mlp_type == "swiglu":
        return {"w_gate": _dl(ks[0], d, cfg.d_ff, cfg),
                "w_up": _dl(ks[1], d, cfg.d_ff, cfg),
                "w_down": _dl(ks[2], cfg.d_ff, d, cfg)}
    return {"w_in": _dl(ks[0], d, cfg.d_ff, cfg),
            "w_down": _dl(ks[1], cfg.d_ff, d, cfg)}


def _init_deployed_block(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"attn": _init_deployed_attn(k1, cfg),
            "ffn": _init_deployed_ffn(k2, cfg),
            "ln1": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16),
            "ln2": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16)}


def _init_deployed_mamba(key, cfg):
    d = cfg.d_model
    d_inner, H, N, P = ssm_mod.dims(cfg)
    ks = jax.random.split(key, 3)
    return {
        "in_proj": _dl(ks[0], d, 2 * d_inner + 2 * N + H, cfg),
        "out_proj": _dl(ks[1], d_inner, d, cfg),
        "conv_w": (jax.random.normal(ks[2], (ssm_mod.CONV_K, d_inner + 2 * N))
                   / 2.0).astype(jnp.bfloat16),
        "conv_b": jnp.zeros((d_inner + 2 * N,), jnp.bfloat16),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": L.norm_init(d_inner, "rmsnorm", jnp.bfloat16),
        "ln": L.norm_init(d, cfg.norm, jnp.bfloat16),
    }


def init_deployed_model(cfg, key) -> dict:
    ks = jax.random.split(key, 5)
    params = {"embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
                        * 0.02).astype(jnp.bfloat16)}
    stack = lambda fn, k, n: jax.vmap(fn)(jax.random.split(k, n))
    if cfg.family in ("dense", "vlm", "moe"):
        params["blocks"] = stack(lambda k: _init_deployed_block(k, cfg),
                                 ks[1], cfg.n_layers)
    elif cfg.family == "ssm":
        params["blocks"] = stack(lambda k: _init_deployed_mamba(k, cfg),
                                 ks[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        params["blocks"] = stack(lambda k: _init_deployed_mamba(k, cfg),
                                 ks[1], cfg.n_layers)
        params["shared_attn"] = _init_deployed_block(ks[2], cfg)
    elif cfg.family == "audio":
        params["enc_blocks"] = stack(
            lambda k: {"attn": _init_deployed_attn(k, cfg),
                       "mlp": _init_deployed_ffn(k, cfg),
                       "ln1": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16),
                       "ln2": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16)},
            ks[1], cfg.n_encoder_layers)
        params["dec_blocks"] = stack(
            lambda k: {"attn": _init_deployed_attn(k, cfg),
                       "xattn": _init_deployed_attn(k, cfg),
                       "mlp": _init_deployed_ffn(k, cfg),
                       "ln1": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16),
                       "ln2": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16),
                       "ln3": L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16)},
            ks[2], cfg.n_layers)
        params["enc_ln_f"] = L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16)
    params["ln_f"] = L.norm_init(cfg.d_model, cfg.norm, jnp.bfloat16)
    params["lm_head"] = _dl(ks[3], cfg.d_model, cfg.vocab_size, cfg)
    return params


# ---------------------------------------------------------------------------
# Serving forward passes
# ---------------------------------------------------------------------------

def _dq(cd, backend="jnp"):
    return lambda x, dp: dq_linear(x, dp, cd, backend)


def kv_specs(cfg, kv_bits):
    """Resolve the ``kv_bits`` cache policy knob into per-site channel-group
    specs: ``(gqa_spec, mla_spec)``.

    ``kv_bits=None`` keeps the legacy int8-per-token cache contract on every
    ring (``(None, None)``).  An int or bit-tuple builds a
    :class:`~repro.models.kv_quant.KVQuantSpec` over each ring's feature
    axis — ``head_dim`` for GQA K/V (dense/vlm/moe attention, the hybrid
    shared block, audio self+cross) and ``kv_lora_rank`` for the MLA latent.
    ``ssm`` has no per-token ring, so the knob is a no-op there.  Raises at
    resolution time (engine construction) when a feature axis cannot honor
    the requested packing, never inside a jitted step.
    """
    if kv_bits is None or cfg.family == "ssm":
        return None, None
    if cfg.use_mla and cfg.family in ("dense", "vlm", "moe"):
        return None, kvq.spec_for(kv_bits, cfg.kv_lora_rank)
    return kvq.spec_for(kv_bits, cfg.head_dim), None


@jax.named_scope("attn")
def _deployed_attn_full(p, cfg, x, positions, causal=True, enc=None,
                        backend="jnp", build_cache=False, kv_spec=None):
    """Full-seq attention on deployed weights; optionally emit a quantized
    cache (legacy int8 per token, or channel-wise packed under ``kv_spec``)."""
    B, S, _ = x.shape
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if enc is None else enc
    q = dq(x, p["wq"]).reshape(B, S, H, hd)
    k = dq(src, p["wk"]).reshape(B, src.shape[1], KV, hd)
    v = dq(src, p["wv"]).reshape(B, src.shape[1], KV, hd)
    if enc is None and cfg.rope_partial > 0:
        cos, sin, rot = L.rope_freqs(hd, cfg.rope_theta, positions,
                                     cfg.rope_partial)
        q = L.apply_rope(q, cos, sin, rot)
        k = L.apply_rope(k, cos, sin, rot)
    o = attn.gqa_core(q, k, v, H, KV, causal=causal and enc is None)
    y = dq(o.reshape(B, S, H * hd), p["wo"])
    cache = None
    if build_cache:
        if kv_spec is None:
            kq, ksc = attn.quant_per_token(k.transpose(0, 2, 1, 3))
            vq, vsc = attn.quant_per_token(v.transpose(0, 2, 1, 3))
        else:
            kq, ksc = kvq.quant_channelwise(k.transpose(0, 2, 1, 3), kv_spec)
            vq, vsc = kvq.quant_channelwise(v.transpose(0, 2, 1, 3), kv_spec)
        cache = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    return y, cache


@jax.named_scope("attn")
def _deployed_mla_full(p, cfg, x, positions, backend="jnp",
                       build_cache=False, kv_spec=None):
    B, S, _ = x.shape
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    H = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cq = L.rmsnorm(dq(x, p["wq_a"]), p["q_norm"])
    q = dq(cq, p["wq_b"]).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv = dq(x, p["wkv_a"])
    c_kv, k_rope = ckv[..., :kvr], ckv[..., kvr:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"])
    kv = dq(c_kv, p["wkv_b"]).reshape(B, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin, rot = L.rope_freqs(rope, cfg.rope_theta, positions, 1.0)
    q_rope = L.apply_rope(q_rope, cos, sin, rot)
    k_rope_r = L.apply_rope(k_rope[:, :, None, :], cos, sin, rot)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_r, (B, S, H, rope))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attn.gqa_core(q_full, k_full, v, H, H, causal=True)
    y = dq(o.reshape(B, S, H * vd), p["wo"])
    cache = None
    if build_cache:
        if kv_spec is None:
            qc, qs = attn.quant_per_token(c_kv)
        else:
            qc, qs = kvq.quant_channelwise(c_kv, kv_spec)
        cache = {"ckv": qc, "ckv_scale": qs,
                 "krope": k_rope_r[:, :, 0].astype(jnp.bfloat16)}
    return y, cache


@jax.named_scope("ffn")
def _deployed_ffn_full(p, cfg, x, backend="jnp"):
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    if cfg.n_experts:
        return _deployed_moe(p, cfg, x, backend)
    if cfg.mlp_type == "swiglu":
        h = L.swiglu(dq(x, p["w_gate"]), dq(x, p["w_up"]))
    else:
        h = jax.nn.gelu(dq(x, p["w_in"]))
    return dq(h, p["w_down"])


def _deployed_moe(p, cfg, x, backend="jnp"):
    B, S, d = x.shape
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    E, k, ff = cfg.n_experts, cfg.experts_per_token, cfg.moe_d_ff
    T = B * S
    xt = x.reshape(T, d)
    # mesh serving: the router is the one f32 GEMM on the decode path — its
    # reduction order must not depend on the mesh, so input and weight stay
    # replicated (ShardingRules replicates "router"); identity off-mesh
    xt = shd.replicate_serving(xt)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32).T)
    routing = "sigmoid" if cfg.n_shared_experts else "softmax"
    gates, topi = moe_mod.route_topk(logits, k, routing)
    capacity = max(8, min(int(cfg.capacity_factor * T * k / E), T))
    dest, keep, _ = moe_mod.dispatch_indices(topi.reshape(-1), E, capacity)
    src = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E * capacity, d), cd).at[dest].add(
        jnp.where(keep[:, None], xt[src].astype(cd), 0)).reshape(E, capacity, d)
    # packed grouped expert GEMMs: the expert-stacked QTensors contract the
    # (E, C, d) buffer per expert — ONE expert-batched fused launch each
    # under backend="pallas"; no (E, c_out, c_in) dense stack materializes
    h = L.swiglu(dq(buf, p["we_gate"]), dq(buf, p["we_up"]))
    # mesh serving: the expert GEMMs above run expert-parallel; the combine
    # scatter-adds in cd with duplicate destinations, so it replicates to
    # keep the addition order mesh-independent (identity off-mesh)
    out_buf = shd.replicate_serving(
        dq(h, p["we_down"])).reshape(E * capacity, d)
    gathered = jnp.where(keep[:, None], out_buf[dest], 0)
    out = jnp.zeros((T, d), cd).at[src].add(
        gathered * gates.reshape(-1, 1).astype(cd))
    if cfg.n_shared_experts:
        sp = p["shared"]
        h = L.swiglu(dq(xt, sp["w_gate"]), dq(xt, sp["w_up"]))
        out = out + dq(h, sp["w_down"])
    if cfg.dense_residual_ff:
        dp_ = p["dense_res"]
        h = L.swiglu(dq(xt, dp_["w_gate"]), dq(xt, dp_["w_up"]))
        out = out + dq(h, dp_["w_down"])
    return out.reshape(B, S, d)


@jax.named_scope("ssm")
def _deployed_mamba_full(p, cfg, x, backend="jnp", lens=None):
    """Deployed mamba block; returns (y, final ssm state).

    ``lens``: optional (B,) per-row true prompt lengths for right-padded
    batches.  Padded steps are made exact no-ops on the recurrence by
    zeroing ``dt`` there (``dA = 0`` -> decay 1, ``x*dt = 0`` -> no input),
    so the returned state is the state *at each row's own last real token*;
    the conv ring tail is gathered per row at ``lens`` instead of the
    static trailing slice.  With ``lens`` full (or None) both reductions
    see identical operands, so the padded path is bit-identical to the
    unpadded one.
    """
    B, S, d = x.shape
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    d_inner, H, N, P = ssm_mod.dims(cfg)
    h_in = L.apply_norm(x, p["ln"], cfg.norm)
    zxbcdt = dq(h_in, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc_in = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    xbc = ssm_mod._causal_conv(xbc_in,
                               p["conv_w"].astype(cd), p["conv_b"].astype(cd))
    xs = xbc[..., :d_inner].reshape(B, S, H, P)
    Bm = xbc[..., d_inner:d_inner + N]
    Cm = xbc[..., d_inner + N:]
    dt = jax.nn.softplus(zxbcdt[..., -H:].astype(jnp.float32) + p["dt_bias"])
    if lens is not None:
        pad_mask = jnp.arange(S)[None, :] < lens[:, None]    # (B, S)
        dt = jnp.where(pad_mask[..., None], dt, 0.0)
    A = jnp.exp(p["A_log"])
    y, hT = ssm_mod.ssd_chunked(xs.astype(jnp.float32), dt, A,
                                Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                                cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(cd)
    y = L.rmsnorm(y * jax.nn.silu(z), p["norm"])
    K1 = ssm_mod.CONV_K - 1
    if lens is None:
        conv_tail = xbc_in[:, -K1:]
    else:
        idx = lens[:, None] - K1 + jnp.arange(K1)[None, :]   # (B, K-1)
        tail = jnp.take_along_axis(xbc_in, jnp.maximum(idx, 0)[..., None],
                                   axis=1)
        conv_tail = jnp.where((idx >= 0)[..., None], tail, 0.0)
    return x + dq(y, p["out_proj"]).astype(x.dtype), {
        "h": hT, "conv": conv_tail.astype(jnp.bfloat16)}


def _last_token(x, lens):
    """Per-row last real token of a right-padded batch: (B, S, d) -> (B, 1, d).

    ``lens=None`` keeps the static ``x[:, -1:]`` slice (full-length batch);
    with ``lens`` the gather at ``lens-1`` reads the same elements when the
    row is full-length, so the padded path stays bit-identical there.
    """
    if lens is None:
        return x[:, -1:]
    idx = (jnp.maximum(lens, 1) - 1).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(x, idx, axis=1)


def prefill(dparams, cfg, batch, backend: str = "jnp", lens=None,
            kv_bits=None):
    """Full-sequence deployed forward.  Returns (last-token logits, caches).

    ``lens``: optional (B,) int32 per-row true prompt lengths for a
    right-padded ``tokens`` batch (the continuous-batching admission path —
    api/scheduler.py pads every prompt to one static prefill width so
    admission never re-jits).  Logits are then taken at each row's own
    last real token; SSM states stop at ``lens`` (padded steps are exact
    no-ops); attention caches still carry entries for the padded tail, but
    those sit strictly *above* each slot's position and every decode mask
    is ``<= pos``, and the first ``pos`` advance overwrites index ``lens``
    before it ever becomes visible — so the padding is never attended.

    ``kv_bits``: cache quantization policy (see :func:`kv_specs`) — the
    emitted caches then carry the channel-wise packed layout and must pair
    with ``init_caches``/``init_paged_caches``/``decode_step`` at the SAME
    ``kv_bits``.
    """
    cd = cfg.cdtype
    gqa_spec, mla_spec = kv_specs(cfg, kv_bits)
    if cfg.family == "audio":
        return _prefill_encdec(dparams, cfg, batch, backend, lens, gqa_spec)
    with jax.named_scope("embed"):
        x = dparams["embed"][batch["tokens"]].astype(cd)
        if cfg.n_prefix_tokens and "prefix_embeds" in batch:
            n = cfg.n_prefix_tokens
            x = jnp.concatenate([batch["prefix_embeds"].astype(cd),
                                 x[:, n:]], 1)
    B, S, _ = x.shape
    positions = jnp.arange(S)

    caches = None
    if cfg.family in ("dense", "vlm", "moe"):
        def body(h, p):
            hn = L.apply_norm(h, p["ln1"], cfg.norm)
            if cfg.use_mla:
                a, c = _deployed_mla_full(p["attn"], cfg, hn, positions,
                                          backend, build_cache=True,
                                          kv_spec=mla_spec)
            else:
                a, c = _deployed_attn_full(p["attn"], cfg, hn, positions,
                                           backend=backend, build_cache=True,
                                           kv_spec=gqa_spec)
            h = h + a.astype(h.dtype)
            f = _deployed_ffn_full(p["ffn"], cfg,
                                   L.apply_norm(h, p["ln2"], cfg.norm), backend)
            return h + f.astype(h.dtype), c
        x, caches = jax.lax.scan(body, x, dparams["blocks"])
    elif cfg.family == "ssm":
        def body(h, p):
            h2, st = _deployed_mamba_full(p, cfg, h, backend, lens)
            return h2, st
        x, caches = jax.lax.scan(body, x, dparams["blocks"])
    elif cfg.family == "hybrid":
        caches = {"ssm": [], "attn": []}
        Ltot, kk = cfg.n_layers, cfg.attn_every
        start = 0
        while start < Ltot:
            hn = L.apply_norm(x, dparams["shared_attn"]["ln1"], cfg.norm)
            a, c = _deployed_attn_full(dparams["shared_attn"]["attn"], cfg, hn,
                                       positions, backend=backend,
                                       build_cache=True, kv_spec=gqa_spec)
            x = x + a.astype(x.dtype)
            f = _deployed_ffn_full(
                dparams["shared_attn"]["ffn"], cfg,
                L.apply_norm(x, dparams["shared_attn"]["ln2"], cfg.norm),
                backend)
            x = x + f.astype(x.dtype)
            caches["attn"].append(c)
            stop = min(start + kk, Ltot)
            pg = jax.tree_util.tree_map(lambda t: t[start:stop],
                                        dparams["blocks"])
            def body(h, p):
                h2, st = _deployed_mamba_full(p, cfg, h, backend, lens)
                return h2, st
            x, st = jax.lax.scan(body, x, pg)
            caches["ssm"].append(st)
            start = stop
        caches["attn"] = jax.tree_util.tree_map(
            lambda *t: jnp.stack(t), *caches["attn"])
        caches["ssm"] = jax.tree_util.tree_map(
            lambda *t: jnp.concatenate(t), *caches["ssm"])

    with jax.named_scope("lm_head"):
        x = L.apply_norm(x, dparams["ln_f"], cfg.norm)
        logits = dq_linear(_last_token(x, lens), dparams["lm_head"], cd,
                           backend)
    return logits.astype(jnp.float32), caches


def _prefill_encdec(dparams, cfg, batch, backend, lens=None, kv_spec=None):
    cd = cfg.cdtype
    enc = batch["frames"].astype(cd)
    Se = enc.shape[1]
    enc = enc + L.sinusoidal_positions(Se, cfg.d_model).astype(cd)
    pos_e = jnp.arange(Se)

    def ebody(h, p):
        a, _ = _deployed_attn_full(p["attn"], cfg,
                                   L.apply_norm(h, p["ln1"], cfg.norm), pos_e,
                                   causal=False, backend=backend)
        h = h + a.astype(h.dtype)
        f = _deployed_ffn_full(p["mlp"], cfg,
                               L.apply_norm(h, p["ln2"], cfg.norm), backend)
        return h + f.astype(h.dtype), None
    enc, _ = jax.lax.scan(ebody, enc, dparams["enc_blocks"])
    enc = L.apply_norm(enc, dparams["enc_ln_f"], cfg.norm)

    with jax.named_scope("embed"):
        x = dparams["embed"][batch["tokens"]].astype(cd)
        B, S, _ = x.shape
        x = x + L.sinusoidal_positions(S, cfg.d_model).astype(cd)
    pos = jnp.arange(S)

    def dbody(h, p):
        a, c = _deployed_attn_full(p["attn"], cfg,
                                   L.apply_norm(h, p["ln1"], cfg.norm), pos,
                                   backend=backend, build_cache=True,
                                   kv_spec=kv_spec)
        h = h + a.astype(h.dtype)
        xa, cc = _deployed_attn_full(p["xattn"], cfg,
                                     L.apply_norm(h, p["ln2"], cfg.norm), pos,
                                     enc=enc, backend=backend,
                                     build_cache=True, kv_spec=kv_spec)
        h = h + xa.astype(h.dtype)
        f = _deployed_ffn_full(p["mlp"], cfg,
                               L.apply_norm(h, p["ln3"], cfg.norm), backend)
        return h + f.astype(h.dtype), {"self": c, "cross": cc}
    x, caches = jax.lax.scan(dbody, x, dparams["dec_blocks"])
    with jax.named_scope("lm_head"):
        x = L.apply_norm(x, dparams["ln_f"], cfg.norm)
        logits = dq_linear(_last_token(x, lens), dparams["lm_head"], cd,
                           backend)
    return logits.astype(jnp.float32), caches


# ---------------------------------------------------------------------------
# Decode step (one new token, full KV cache) — the decode_* dry-run workload
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int, kv_bits=None):
    """Empty caches for decode-only dry-runs (shape stand-ins).

    ``kv_bits`` (see :func:`kv_specs`) swaps the ring leaves for the
    channel-wise packed layout — same tree structure, packed-byte dtypes.
    """
    gqa_spec, mla_spec = kv_specs(cfg, kv_bits)
    if cfg.family in ("dense", "vlm", "moe"):
        one = (attn.init_mla_cache(cfg, batch, max_len, mla_spec)
               if cfg.use_mla
               else attn.init_gqa_cache(cfg, batch, max_len, gqa_spec))
        return jax.tree_util.tree_map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), one)
    if cfg.family == "ssm":
        one = ssm_mod.init_ssm_cache(cfg, batch)
        return jax.tree_util.tree_map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), one)
    if cfg.family == "hybrid":
        ssm_one = ssm_mod.init_ssm_cache(cfg, batch)
        attn_one = attn.init_gqa_cache(cfg, batch, max_len, gqa_spec)
        n_groups = -(-cfg.n_layers // cfg.attn_every)
        return {
            "ssm": jax.tree_util.tree_map(
                lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype), ssm_one),
            "attn": jax.tree_util.tree_map(
                lambda t: jnp.zeros((n_groups,) + t.shape, t.dtype), attn_one),
        }
    if cfg.family == "audio":
        self_c = attn.init_gqa_cache(cfg, batch, max_len, gqa_spec)
        cross_c = attn.init_gqa_cache(cfg, batch, cfg.encoder_seq, gqa_spec)
        # Zero-scale decode-only contract: this cross cache ships all-zero
        # int8 values AND all-zero per-token scales, so the dequantized
        # encoder KV is exactly 0 and cross-attention softmaxes to uniform
        # weights over encoder positions — a shape stand-in for decode-only
        # dry-runs, never a real serving state.  Real generation embeds the
        # prefill's encoder-built cross cache over these zeros
        # (embed_caches / merge_paged_caches).
        return jax.tree_util.tree_map(
            lambda t: jnp.zeros((cfg.n_layers,) + t.shape, t.dtype),
            {"self": self_c, "cross": cross_c})
    raise ValueError(cfg.family)


def supports_paging(cfg) -> bool:
    """Whether the family has a ring axis the paged KV cache can page.

    ``ssm`` is pure recurrent state (no per-token ring), so the engine
    silently serves it dense; ``hybrid`` pages only its attention subtree
    and ``audio`` only the decoder self-attention ring (the cross cache is
    encoder-length per slot, written once at admission).
    """
    return cfg.family in ("dense", "vlm", "moe", "hybrid", "audio")


def supports_speculative(cfg) -> bool:
    """Whether the family can run as a speculative draft/verifier.

    Needs (a) a multi-token verify path — attention rings rewind for free
    (rejected entries stay masked by ``<= pos`` until overwritten) but
    recurrent SSM state cannot un-apply a token, ruling out ``ssm`` and
    ``hybrid`` — and (b) a decode path that is width-generic (``audio``'s
    cross-attention decode is hardcoded to one query token).
    """
    return cfg.family in ("dense", "vlm", "moe")


def draft_model(dparams, cfg, draft_bits: int):
    """Derive a low-bit draft policy from a deployed verifier param tree.

    Every :class:`QTensor` leaf is re-quantized to a uniform ``draft_bits``
    channel assignment (api/qtensor.requantize) — the one-checkpoint-many-
    precisions trick: the aggressive end of the paper's channel-wise Pareto
    front drafts, the searched 8-bit deploy verifies.  Non-QTensor leaves
    (the embedding / lm_head table, norms, biases) are shared **by
    reference** with the verifier tree, so the draft costs only the packed
    low-bit linears.
    """
    from repro.api.qtensor import requantize
    if not supports_speculative(cfg):
        raise ValueError(
            f"family {cfg.family!r} cannot draft (see supports_speculative)")
    return jax.tree_util.tree_map(
        lambda leaf: (requantize(leaf, draft_bits)
                      if isinstance(leaf, QTensor) else leaf),
        dparams, is_leaf=lambda leaf: isinstance(leaf, QTensor))


def init_paged_caches(cfg, max_slots: int, num_pages: int, page_size: int,
                      kv_bits=None):
    """Paged serving caches: ring leaves become physical page pools.

    Each paged leaf swaps its per-slot ``(max_slots, .., max_len, F)`` ring
    for ``(num_pages, .., page_size, F)`` — same tree structure as
    :func:`init_caches`, so the decode scan is unchanged; only the batch
    axis meaning differs (physical pages indexed through the scheduler's
    page table instead of slots).  Page 0 is the NULL page: never written,
    always zero (repro/cache).  Non-ring leaves (hybrid SSM state, audio
    cross caches) keep their per-slot layout.

    ``kv_bits`` packs the page pools channel-wise (:func:`kv_specs`): the
    packing is feature-axis only, so a page boundary never splits a packed
    byte and the page-table machinery is unchanged — pages just carry fewer
    bytes per token.
    """
    gqa_spec, mla_spec = kv_specs(cfg, kv_bits)
    stackN = lambda one, n: jax.tree_util.tree_map(
        lambda t: jnp.zeros((n,) + t.shape, t.dtype), one)
    if cfg.family in ("dense", "vlm", "moe"):
        one = (attn.init_mla_cache(cfg, num_pages, page_size, mla_spec)
               if cfg.use_mla
               else attn.init_gqa_cache(cfg, num_pages, page_size, gqa_spec))
        return stackN(one, cfg.n_layers)
    if cfg.family == "hybrid":
        n_groups = -(-cfg.n_layers // cfg.attn_every)
        return {
            "ssm": stackN(ssm_mod.init_ssm_cache(cfg, max_slots),
                          cfg.n_layers),
            "attn": stackN(attn.init_gqa_cache(cfg, num_pages, page_size,
                                               gqa_spec),
                           n_groups),
        }
    if cfg.family == "audio":
        # cross keeps the zero-scale stand-in contract of init_caches; real
        # serving admit-merges the prefill's encoder-built cross cache in.
        return stackN({"self": attn.init_gqa_cache(cfg, num_pages, page_size,
                                                   gqa_spec),
                       "cross": attn.init_gqa_cache(cfg, max_slots,
                                                    cfg.encoder_seq,
                                                    gqa_spec)},
                      cfg.n_layers)
    raise ValueError(f"family {cfg.family!r} has no paged cache layout "
                     "(see supports_paging)")


def paged_leaf_mask(cfg):
    """Bool tree over the serving cache structure: True = page-pool leaf
    (indexed through the page table), False = per-slot leaf (admit-merged
    and decoded exactly as in the dense engine)."""
    tmap = jax.tree_util.tree_map
    if cfg.family in ("dense", "vlm", "moe"):
        one = (attn.init_mla_cache(cfg, 1, 1) if cfg.use_mla
               else attn.init_gqa_cache(cfg, 1, 1))
        return tmap(lambda t: True, one)
    if cfg.family == "hybrid":
        return {"ssm": tmap(lambda t: False, ssm_mod.init_ssm_cache(cfg, 1)),
                "attn": tmap(lambda t: True, attn.init_gqa_cache(cfg, 1, 1))}
    if cfg.family == "audio":
        one = attn.init_gqa_cache(cfg, 1, 1)
        return {"self": tmap(lambda t: True, one),
                "cross": tmap(lambda t: False, one)}
    raise ValueError(f"family {cfg.family!r} has no paged cache layout "
                     "(see supports_paging)")


@jax.named_scope("kv_write")
def merge_paged_caches(cfg, prefill_caches, caches, slot, wp_flat):
    """Admit one request's prefill into the paged caches — the paged
    counterpart of :func:`write_slot` in the dense engine.

    Page-pool leaves scatter whole prompt pages through ``wp_flat
    (n_pp,)`` (``cache.paged.scatter_prefill``): the junk tail past a short
    prompt and prefix-shared (read-only) pages carry the out-of-bounds
    sentinel and are dropped.  Per-slot leaves (hybrid SSM state, audio
    cross) are written at ``slot`` by :func:`write_slot`; every other slot
    is neither read nor written.
    """
    def one(m, pc, full):
        if m:
            return paged.scatter_prefill(full, pc, wp_flat)
        return write_slot(pc, full, slot)
    return jax.tree_util.tree_map(one, paged_leaf_mask(cfg),
                                  prefill_caches, caches)


@jax.named_scope("kv_write")
def embed_caches(prefill_caches, ring):
    """Right-pad the S-deep prefill caches into the max_len ring.

    Each leaf differs from its ring counterpart in at most the sequence
    axis; zero-padding IS the empty-slot convention (decode masks by
    position), so generation really attends to the prompt.  ``ring`` only
    lends its shapes and dtypes (arrays or ``jax.ShapeDtypeStruct``).  One
    embedding rule shared by the request-level scheduler's admission
    (:func:`write_slot`, api/scheduler.py) and the lockstep oracle loops
    over ``engine.serving_jits`` (paged engines scatter their ring pages
    via :func:`merge_paged_caches` instead).
    """
    def one(pc, full):
        if pc.shape == full.shape:
            return pc.astype(full.dtype)
        diff = [i for i, (a, b) in enumerate(zip(pc.shape, full.shape))
                if a != b]
        assert len(diff) == 1, (pc.shape, full.shape)
        widths = [(0, 0)] * pc.ndim
        widths[diff[0]] = (0, full.shape[diff[0]] - pc.shape[diff[0]])
        return jnp.pad(pc, widths).astype(full.dtype)
    return jax.tree_util.tree_map(one, prefill_caches, ring)


@jax.named_scope("kv_write")
def write_slot(prefill_caches, caches, slot):
    """Write one request's prefill caches into slot ``slot`` of the stacked
    serving caches (batch on axis 1) and leave every other slot as it is.

    The batch-1 prefill is embedded into a one-slot ring
    (:func:`embed_caches`) and written with one ``dynamic_update_slice``
    per leaf at the traced ``slot``: the other slots' state is neither
    read nor rewritten, so one admission program serves every slot.
    """
    ring1 = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape[:1] + (1,) + t.shape[2:],
                                       t.dtype), caches)
    return jax.tree_util.tree_map(
        lambda new, old: jax.lax.dynamic_update_slice_in_dim(old, new, slot,
                                                             axis=1),
        embed_caches(prefill_caches, ring1), caches)


@jax.named_scope("attn")
def _cross_decode(p, cfg, x, cache, backend, kv_spec=None):
    """Cross-attention decode: query new token against the cached encoder KV.

    Query heads fold to ``(B, KV, rep, hd)`` groups so the encoder KV stays
    at its ``KV`` kv-heads inside the einsums — no ``jnp.repeat`` ever
    materializes the ``rep``-fold redundant f32 encoder tensors (the head
    broadcast happens in the contraction).  Under ``kv_spec`` the encoder
    rings are channel-wise packed; zero codes dequantize to exact 0.0 under
    any scale, so the decode-only zero-scale cross-cache stand-in (all-zero
    packed bytes AND zero scales — see :func:`init_caches`) is preserved
    exactly on the packed path too.
    """
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = H // KV
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    # (B, H, hd) is head-major, so the group fold/unfold is a pure reshape
    qg = dq(x, p["wq"]).reshape(B, H, hd).reshape(B, KV, rep, hd)
    if kv_spec is None:
        kf = (cache["k"].astype(jnp.float32) * cache["k_scale"]).astype(cd)
        vf = (cache["v"].astype(jnp.float32) * cache["v_scale"]).astype(cd)
    else:
        kf = kvq.dequant_channelwise(cache["k"], cache["k_scale"], kv_spec, cd)
        vf = kvq.dequant_channelwise(cache["v"], cache["v_scale"], kv_spec, cd)
    s = jnp.einsum("bgrd,bgkd->bgrk", qg, kf).astype(jnp.float32)
    s = s / np.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    o = jnp.einsum("bgrk,bgkd->bgrd", w, vf)
    return dq(o.reshape(B, 1, H * hd), p["wo"])


def decode_step(dparams, cfg, tokens, caches, pos, backend: str = "jnp",
                live=None, pages=None, page_size=None, kv_bits=None):
    """One decode step: tokens (B, W) -> (logits (B, W, V), caches').

    ``W`` is normally 1.  ``W > 1`` is the speculative **verify** launch:
    row ``b``'s token ``j`` is scored at position ``pos[b] + j`` (the
    attention multi-token path writes all W KV entries in one scatter and
    masks per step — see models/attention._gqa_decode_multi), supported for
    the ``dense``/``vlm``/``moe`` families only (:func:`supports_speculative`).

    ``pos`` is a **per-slot position vector** (B,) int32: row ``b`` writes
    its new cache entry at its own ring index ``pos[b]`` and attends to
    ``<= pos[b]`` — independently-progressed requests (continuous
    batching, api/scheduler.py) decode in ONE fixed-width launch.  A
    scalar ``pos`` is accepted for migration and broadcasts to the
    all-slots-synchronized vector (see docs/serving.md).

    ``live``: optional (B,) bool slot mask — rows with ``live=False``
    (freed slots awaiting re-admission) leave every cache untouched:
    attention/MLA ring writes are dropped and SSM state updates are
    slot-masked.  Their logits row is garbage and must be ignored.

    ``pages``: optional (B, P) int32 page table — ``caches`` then hold the
    paged layout of :func:`init_paged_caches` (``P * page_size ==
    max_len``) and every ring read/write routes through the table; the
    gathered per-slot view is exactly the dense ring, so logits are
    bit-identical to the dense path.  Non-ring leaves ignore the table.

    ``kv_bits``: cache quantization policy (:func:`kv_specs`); must match
    the policy the caches were built with.  Under ``backend="pallas"`` the
    packed GQA rings decode through the fused dequant decode-attention
    kernel (kernels/decode_attention.py).
    """
    gqa_spec, mla_spec = kv_specs(cfg, kv_bits)
    cd = cfg.cdtype
    dq = _dq(cd, backend)
    with jax.named_scope("embed"):
        x = dparams["embed"][tokens].astype(cd)
    B = tokens.shape[0]
    if tokens.shape[1] > 1 and not supports_speculative(cfg):
        raise ValueError(
            f"family {cfg.family!r} has no multi-token verify path "
            "(see supports_speculative)")
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:                 # legacy scalar: all slots synchronized
        pos = jnp.broadcast_to(pos[None], (B,))

    if cfg.family in ("dense", "vlm", "moe"):
        def body(h, pc):
            p, c = pc
            hn = L.apply_norm(h, p["ln1"], cfg.norm)
            if cfg.use_mla:
                a, c2 = attn.mla_decode(p["attn"], cfg, hn, c, pos, dq, live,
                                        pages, page_size, mla_spec)
            else:
                a, c2 = attn.gqa_decode(p["attn"], cfg, hn, c, pos, dq, live,
                                        pages, page_size, gqa_spec, backend)
            h = h + a.astype(h.dtype)
            f = _deployed_ffn_full(p["ffn"], cfg,
                                   L.apply_norm(h, p["ln2"], cfg.norm), backend)
            return h + f.astype(h.dtype), c2
        x, caches = jax.lax.scan(body, x, (dparams["blocks"], caches))
    elif cfg.family == "ssm":
        def body(h, pc):
            p, c = pc
            hn = L.apply_norm(h, p["ln"], cfg.norm)
            y, c2 = ssm_mod.mamba2_decode(p, cfg, hn, c, dq, live)
            return h + y.astype(h.dtype), c2
        x, caches = jax.lax.scan(body, x, (dparams["blocks"], caches))
    elif cfg.family == "hybrid":
        Ltot, kk = cfg.n_layers, cfg.attn_every
        new_attn, new_ssm = [], []
        start, g = 0, 0
        while start < Ltot:
            c_att = jax.tree_util.tree_map(lambda t: t[g], caches["attn"])
            hn = L.apply_norm(x, dparams["shared_attn"]["ln1"], cfg.norm)
            a, c2 = attn.gqa_decode(dparams["shared_attn"]["attn"], cfg,
                                    hn, c_att, pos, dq, live, pages,
                                    page_size, gqa_spec, backend)
            x = x + a.astype(x.dtype)
            f = _deployed_ffn_full(
                dparams["shared_attn"]["ffn"], cfg,
                L.apply_norm(x, dparams["shared_attn"]["ln2"], cfg.norm),
                backend)
            x = x + f.astype(x.dtype)
            new_attn.append(c2)
            stop = min(start + kk, Ltot)
            pg = jax.tree_util.tree_map(lambda t: t[start:stop],
                                        dparams["blocks"])
            cg = jax.tree_util.tree_map(lambda t: t[start:stop], caches["ssm"])
            def body(h, pc):
                p, c = pc
                hn2 = L.apply_norm(h, p["ln"], cfg.norm)
                y, cn = ssm_mod.mamba2_decode(p, cfg, hn2, c, dq, live)
                return h + y.astype(h.dtype), cn
            x, cs = jax.lax.scan(body, x, (pg, cg))
            new_ssm.append(cs)
            start, g = stop, g + 1
        caches = {
            "attn": jax.tree_util.tree_map(lambda *t: jnp.stack(t), *new_attn),
            "ssm": jax.tree_util.tree_map(lambda *t: jnp.concatenate(t),
                                          *new_ssm),
        }
    elif cfg.family == "audio":
        def body(h, pc):
            p, c = pc
            hn = L.apply_norm(h, p["ln1"], cfg.norm)
            a, c2 = attn.gqa_decode(p["attn"], cfg, hn, c["self"], pos, dq,
                                    live, pages, page_size, gqa_spec, backend)
            h = h + a.astype(h.dtype)
            xa = _cross_decode(p["xattn"], cfg,
                               L.apply_norm(h, p["ln2"], cfg.norm), c["cross"],
                               backend, gqa_spec)
            h = h + xa.astype(h.dtype)
            f = _deployed_ffn_full(p["mlp"], cfg,
                                   L.apply_norm(h, p["ln3"], cfg.norm), backend)
            return h + f.astype(h.dtype), {"self": c2, "cross": c["cross"]}
        x, caches = jax.lax.scan(body, x, (dparams["dec_blocks"], caches))

    with jax.named_scope("lm_head"):
        x = L.apply_norm(x, dparams["ln_f"], cfg.norm)
        logits = dq_linear(x, dparams["lm_head"], cd, backend)
    return logits.astype(jnp.float32), caches
