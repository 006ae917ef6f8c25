"""Expert-axis (batched) fused GEMM parity harness (PR 4).

``models/serving.init_deployed_linear(expert_axis=E)`` stacks every QTensor
leaf with a leading expert axis and builds per-expert fused buffers under
ONE static tile schedule; ``QTensor.matmul`` then dispatches
``einsum("ecd,efd->ecf")``-shaped grouped expert GEMMs as a single
expert-batched ``pallas_call`` (kernels/quant_matmul.quant_matmul_fused_3d,
grid ``(E, M/bm, T)``).

The acceptance contract is deliberately different from the single-weight
fused path: the expert kernel dequantizes each weight tile in VMEM *before*
the MXU dot, so at f32 compute its output is **bit-exact with the dense
einsum reference** it replaced (the removed ``dq_expert_weights`` +
``jnp.einsum`` hot path) — while HBM weight traffic stays the packed
sub-byte bytes.  The per-group Pallas reference path scales the
accumulator after the dot and agrees to f32 roundoff.

Also pinned here: launch-count guards (ONE ``pallas_call`` per expert
site, counted in the traced jaxpr), the ``_init_deployed_ffn`` RNG-key
regression (``shared`` and ``dense_res`` sub-trees must differ), and the
packed-MLA-decode vs weight-absorption reference regression.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DeploySpec, get_config
from repro.kernels import ops
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import serving

REF_TOL = 1e-5


def _cfg(fractions=(0.25, 0.55, 0.20), align=8):
    cfg = get_config("deepseek-v3-671b").reduced()
    return dataclasses.replace(
        cfg, deploy=DeploySpec(fractions=fractions, align=align,
                               act_bits=cfg.deploy.act_bits,
                               kv_cache_bits=cfg.deploy.kv_cache_bits))


def _expert_site(seed, E, c_out, c_in, cfg, tile_n="auto"):
    dp = serving.init_deployed_linear(jax.random.PRNGKey(seed), c_in, c_out,
                                      cfg, expert_axis=E, tile_n=tile_n)
    return dp["w"]


def _x(seed, E, m, c_in):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((E, m, c_in)),
        jnp.float32)


# (name, E, c_out, c_in, fractions, tile_n)
CASES = [
    ("E1-aligned", 1, 48, 32, (0.25, 0.55, 0.20), "auto"),
    ("E4-off-tile-ff-d", 4, 50, 33, (0.25, 0.55, 0.20), "auto"),
    ("E8-low-bit-heavy", 8, 24, 20, (0.50, 0.25, 0.25), "auto"),
    ("E4-single-group-8b", 4, 40, 16, (0.0, 0.0, 1.0), "auto"),
    # explicit tile_n=16 makes the middle group (24 rows) off-tile: tile
    # padding lands *inside* the walk, forcing the batched output gather
    ("E4-output-gather", 4, 50, 33, (0.25, 0.55, 0.20), 16),
]


@pytest.mark.parametrize("name,E,c_out,c_in,fractions,tile_n", CASES,
                         ids=[c[0] for c in CASES])
def test_expert_fused_bitexact_with_dense_einsum_reference(
        name, E, c_out, c_in, fractions, tile_n):
    """Acceptance: ONE expert-batched launch == the dense einsum it
    replaced, bit for bit at f32 — for seeded bit mixes, E in {1, 4, 8}
    and off-tile ff/d shapes."""
    qt = _expert_site(11, E, c_out, c_in, _cfg(fractions), tile_n)
    assert qt.experts == E and qt.fused_packed is not None
    assert qt.fused_packed.shape[0] == E
    if name == "E4-output-gather":
        assert qt.fused_perm is not None     # really exercises the gather
    w_dense = qt.dequantize(jnp.float32)     # (E, c_out, c_in) — test-only
    assert w_dense.shape == (E, c_out, c_in)
    # m >= 2 only: XLA dispatches an M=1 contraction to a matvec whose K
    # reduction associates differently from the kernel's (M-padded) GEMM,
    # so bit-equality with the unpadded reference holds on GEMM-shaped
    # inputs.  That IS the serving contract — _deployed_moe always
    # contracts capacity >= 8 rows per expert; m=1 stays covered at f32
    # roundoff by test_expert_backends_agree.
    for m in (5, 8, 130):
        x = _x(m, E, m, c_in)
        y_fused = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
        y_ref = np.asarray(jnp.einsum("ecd,efd->ecf", x, w_dense))
        np.testing.assert_array_equal(y_fused, y_ref,
                                      err_msg=f"{name} m={m}")
        assert y_fused.shape == (E, m, c_out)


# 128-wide tiles run one per grid step (the LM-width kernel layout), where
# the CPU GEMM's rounding by output width leaves f32-roundoff agreement
AGREE_CASES = CASES + [
    ("E2-128-wide-tiles", 2, 512, 512, (0.25, 0.55, 0.20), 128),
]


@pytest.mark.parametrize("name,E,c_out,c_in,fractions,tile_n", AGREE_CASES,
                         ids=[c[0] for c in AGREE_CASES])
def test_expert_backends_agree(name, E, c_out, c_in, fractions, tile_n):
    """Fused vs per-group-per-expert Pallas vs jnp: same math, different
    scale placement — f32-roundoff agreement (per-group scales the
    accumulator after the dot, PR 3 style)."""
    qt = _expert_site(13, E, c_out, c_in, _cfg(fractions), tile_n)
    for m in (1, 6):
        x = _x(17 + m, E, m, c_in)
        y_fused = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
        y_pg = np.asarray(qt.matmul(x, jnp.float32,
                                    backend="pallas-pergroup"))
        y_jnp = np.asarray(qt.matmul(x, jnp.float32, backend="jnp"))
        scale = max(1.0, np.abs(y_jnp).max())
        np.testing.assert_allclose(y_fused, y_pg, atol=REF_TOL * scale,
                                   rtol=REF_TOL, err_msg=f"{name} m={m}")
        np.testing.assert_allclose(y_fused, y_jnp, atol=REF_TOL * scale,
                                   rtol=REF_TOL, err_msg=f"{name} m={m}")


def test_expert_matmul_rejects_bad_leading_axis():
    qt = _expert_site(3, 4, 24, 16, _cfg())
    with pytest.raises(ValueError, match="expert"):
        qt.matmul(jnp.zeros((3, 5, 16)), backend="pallas")   # wrong E
    with pytest.raises(ValueError, match="contraction"):
        qt.matmul(jnp.zeros((4, 5, 12)), backend="pallas")   # wrong c_in


# ---------------------------------------------------------------------------
# Launch-count guards: ONE pallas_call per expert site
# ---------------------------------------------------------------------------

def test_expert_site_is_one_launch():
    """The batched grid covers E: one fused launch serves all experts of a
    site, while the per-group reference pays E launches per precision
    group."""
    E = 4
    qt = _expert_site(7, E, 50, 33, _cfg())
    x = _x(2, E, 6, 33)
    n_groups = len(qt.bits)
    assert n_groups > 1
    fused = ops.count_pallas_launches(
        lambda x: qt.matmul(x, jnp.float32, backend="pallas"), x)
    pg = ops.count_pallas_launches(
        lambda x: qt.matmul(x, jnp.float32, backend="pallas-pergroup"), x)
    assert fused == 1
    assert pg == E * n_groups
    assert ops.count_pallas_launches(
        lambda x: qt.matmul(x, jnp.float32, backend="jnp"), x) == 0


def test_deployed_moe_ffn_is_one_launch_per_site():
    """Whole deployed MoE FFN (routed experts + shared expert): exactly one
    pallas_call per QTensor site on the fused backend."""
    from repro.api.qtensor import QTensor
    cfg = get_config("deepseek-v3-671b").reduced()
    p = serving._init_deployed_ffn(jax.random.PRNGKey(0), cfg)
    sites = [t for t in jax.tree_util.tree_leaves(
        p, is_leaf=lambda t: isinstance(t, QTensor))
        if isinstance(t, QTensor)]
    assert all(qt.fused_packed is not None for qt in sites)
    assert sum(qt.experts == cfg.n_experts for qt in sites) == 3
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 8, cfg.d_model)), jnp.float32)
    n = ops.count_pallas_launches(
        lambda x: serving._deployed_ffn_full(p, cfg, x, backend="pallas"), x)
    assert n == len(sites), (n, len(sites))


# ---------------------------------------------------------------------------
# _init_deployed_ffn RNG-key regression (satellite bugfix)
# ---------------------------------------------------------------------------

def test_shared_and_dense_res_ffn_weights_differ():
    """Pre-PR4, a config with BOTH a shared expert and a dense residual MLP
    reused RNG keys ks[4..6] for the two sub-trees, deploying identical
    weights.  Pin sff == rff so the shapes match and the packed bytes must
    still differ."""
    cfg = get_config("deepseek-v3-671b").reduced()
    rff = cfg.moe_d_ff * 2
    cfg = dataclasses.replace(cfg, n_shared_experts=2, dense_residual_ff=rff)
    p = serving._init_deployed_ffn(jax.random.PRNGKey(0), cfg)
    assert "shared" in p and "dense_res" in p
    for name in ("w_gate", "w_up", "w_down"):
        qa, qb = p["shared"][name]["w"], p["dense_res"][name]["w"]
        assert qa.c_out == qb.c_out and qa.c_in == qb.c_in, name
        assert any(not np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(qa.packed, qb.packed)), \
            f"shared and dense_res {name} deployed identical weights"


# ---------------------------------------------------------------------------
# Packed MLA decode vs the removed weight-absorption reference
# ---------------------------------------------------------------------------

def _absorbed_mla_decode(p, cfg, x, cache, pos, dq_linear, dense_w):
    """The pre-PR4 decode math: wkv_b absorbed per head from a dense view.

    Absorption is an exact linear-algebra rewrite of latent expansion, so
    the packed path must reproduce it to f32 roundoff."""
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cd = cfg.cdtype
    cq = L.rmsnorm(dq_linear(x, p["wq_a"]), p["q_norm"])
    q = dq_linear(cq, p["wq_b"]).reshape(B, 1, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckv_new = dq_linear(x, p["wkv_a"])
    c_kv, k_rope_new = ckv_new[..., :kvr], ckv_new[..., kvr:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"])
    cos, sin, rot = L.rope_freqs(rope, cfg.rope_theta, pos[None], 1.0)
    q_rope = L.apply_rope(q_rope, cos, sin, rot)
    k_rope_new = L.apply_rope(k_rope_new[:, :, None, :], cos, sin, rot)[:, :, 0]
    qc, qs = attn.quant_per_token(c_kv)
    pos0 = pos.astype(jnp.int32)
    cache = {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], qc, (0, pos0, 0)),
        "ckv_scale": jax.lax.dynamic_update_slice(cache["ckv_scale"], qs,
                                                  (0, pos0, 0)),
        "krope": jax.lax.dynamic_update_slice(
            cache["krope"], k_rope_new.astype(jnp.bfloat16), (0, pos0, 0)),
    }
    S = cache["ckv"].shape[1]
    wkv_b = dense_w("wkv_b").reshape(H, nope + vd, kvr)
    w_uk, w_uv = wkv_b[:, :nope], wkv_b[:, nope:]
    q_lat = jnp.einsum("bqhn,hnr->bqhr", q_nope.astype(cd), w_uk.astype(cd))
    ckv_f = (cache["ckv"].astype(jnp.float32) * cache["ckv_scale"]).astype(cd)
    s = jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_f).astype(jnp.float32)
    s = s + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(cd),
                       cache["krope"].astype(cd)).astype(jnp.float32)
    s = s / math.sqrt(nope + rope)
    valid = jnp.arange(S)[None, None, None, :] <= pos0
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", w, ckv_f)
    o = jnp.einsum("bqhr,hvr->bqhv", o_lat, w_uv.astype(cd))
    return dq_linear(o.reshape(B, 1, H * vd), p["wo"]), cache


def test_packed_mla_decode_matches_absorption_reference():
    cfg = dataclasses.replace(get_config("deepseek-v3-671b").reduced(),
                              compute_dtype="float32")
    p = serving._init_deployed_attn(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    rng = np.random.default_rng(0)
    cache = attn.init_mla_cache(cfg, B, S)
    for i in range(4):                       # pretend 4 tokens were decoded
        ck = jnp.asarray(rng.standard_normal((B, 1, cfg.kv_lora_rank)) * .5,
                         jnp.float32)
        qc, qs = attn.quant_per_token(ck)
        cache["ckv"] = cache["ckv"].at[:, i].set(qc[:, 0])
        cache["ckv_scale"] = cache["ckv_scale"].at[:, i].set(qs[:, 0])
        cache["krope"] = cache["krope"].at[:, i].set(jnp.asarray(
            rng.standard_normal((B, cfg.qk_rope_dim)), jnp.bfloat16))
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)) * .3,
                    jnp.float32)
    dq = serving._dq(cfg.cdtype, "jnp")
    pos = jnp.asarray(4)
    y_new, c_new = attn.mla_decode(p, cfg, x, cache, pos, dq)
    y_ref, c_ref = _absorbed_mla_decode(
        p, cfg, x, cache, pos, dq,
        lambda n: serving.debug_dense_view(p[n], cfg.cdtype))
    scale = max(1.0, float(jnp.abs(y_ref).max()))
    np.testing.assert_allclose(np.asarray(y_new, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=2e-5 * scale, rtol=2e-5)
    for k in c_new:                          # cache writes are identical
        np.testing.assert_array_equal(np.asarray(c_new[k]),
                                      np.asarray(c_ref[k]))
