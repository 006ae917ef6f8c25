"""Differential parity harness for the fused single-launch GEMM kernel.

Four implementations of the same deployed mixed-precision linear map are
run against each other:

  fused             — ONE pallas_call over the tile-aligned ragged buffer
                      (kernels/quant_matmul.quant_matmul_fused_2d),
                      ``backend="pallas"``
  per-group         — one pallas_call per precision group + concat +
                      order restore, ``backend="pallas-pergroup"``
  jnp               — per-group dense fallback, ``backend="jnp"``
  frozen reference  — fake-quant float weights (the fine-tune phase's view
                      of the same integer grid), plain einsum

At ``compute_dtype=f32`` the fused and per-group paths reduce K in a
single dot of identical length (kernels/quant_matmul.pick_bk — the
bit-exactness contract), so they must agree **bit-exactly**; the jnp and
frozen references differ only in where the per-channel scale is applied
(before vs after the dot), so they agree to f32 roundoff.

Sweeps are seeded-numpy parametrized (no ``hypothesis``): bit mixes over
{2,4,8}, off-tile N/K, single-group, all-8-bit, and one-channel-group
edge cases, plus the four MLPerf-Tiny configs end-to-end and the
launch-count guards (exactly one pallas_call per deployed linear/conv).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, PrecisionPolicy, QTensor
from repro.data import pipeline as pipe
from repro.kernels import ops
from repro.models import tinyml

REF_TOL = 1e-5          # vs jnp / frozen fake-quant (scale-placement ulps)


def _mk_qtensor(seed, c_out, c_in, bits_per_channel, tile_n, align=1,
                restore_order=True):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((c_out, c_in)).astype(np.float32)
    alpha = np.abs(w).max(-1)
    return w, QTensor.from_assignment(w, bits_per_channel, alpha, align=align,
                                      restore_order=restore_order,
                                      tile_n=tile_n)


def _x(seed, m, c_in):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((m, c_in)),
                       jnp.float32)


CASES = [
    # (name, c_out, c_in, tile_n, bits_fn)
    ("mixed-248", 40, 64, 16, lambda rng, n: rng.choice([2, 4, 8], size=n)),
    ("off-tile-N-K", 50, 33, 16, lambda rng, n: rng.choice([2, 4, 8], size=n)),
    ("single-group-4b", 24, 32, 8, lambda rng, n: np.full(n, 4)),
    ("all-8-bit", 20, 48, 16, lambda rng, n: np.full(n, 8)),
    ("one-channel-group", 17, 20, 8,
     lambda rng, n: np.asarray([2] + [8] * (n - 1))),
    ("two-bit-heavy-tiny-K", 9, 5, 4,
     lambda rng, n: rng.choice([2, 4], size=n, p=[0.8, 0.2])),
    # 128-wide tiles: one tile per grid step, and 128-lane-aligned column
    # blocks (K=512), so same-width tiles share a branch at a column offset
    # computed from the grid index
    ("128-wide-tile-runs", 768, 512, 128,
     lambda rng, n: np.repeat([2, 4, 8], n // 3)),
]


@pytest.mark.parametrize("name,c_out,c_in,tile_n,bits_fn", CASES,
                         ids=[c[0] for c in CASES])
def test_fused_vs_pergroup_bitexact(name, c_out, c_in, tile_n, bits_fn):
    """Fused single-launch == per-group Pallas, bit for bit (f32 compute)."""
    rng = np.random.default_rng(sum(ord(c) for c in name))
    bits = bits_fn(rng, c_out)
    _, qt = _mk_qtensor(11, c_out, c_in, bits, tile_n)
    assert qt.fused_packed is not None and qt.tile_n == tile_n
    for m in (1, 5, 130):
        x = _x(m, m, c_in)
        y_fused = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
        y_pg = np.asarray(qt.matmul(x, jnp.float32,
                                    backend="pallas-pergroup"))
        np.testing.assert_array_equal(y_fused, y_pg, err_msg=f"{name} m={m}")
        assert y_fused.shape == (m, c_out)


@pytest.mark.parametrize("name,c_out,c_in,tile_n,bits_fn", CASES,
                         ids=[c[0] for c in CASES])
def test_fused_vs_jnp_and_frozen_reference(name, c_out, c_in, tile_n,
                                           bits_fn):
    """Fused vs the jnp backend and the fake-quant float reference."""
    rng = np.random.default_rng(sum(ord(c) for c in name) + 1)
    bits = bits_fn(rng, c_out)
    w, qt = _mk_qtensor(13, c_out, c_in, bits, tile_n)
    x = _x(17, 6, c_in)
    y_fused = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
    y_jnp = np.asarray(qt.matmul(x, jnp.float32, backend="jnp"))
    # fake-quant reference: same integer grid, canonical order, scale
    # applied to the weight before the dot
    w_ref = qt.dequantize_canonical(jnp.float32)
    y_ref = np.asarray(x @ w_ref.T)
    scale = max(1.0, np.abs(y_ref).max())
    np.testing.assert_allclose(y_fused, y_jnp, atol=REF_TOL * scale,
                               rtol=REF_TOL, err_msg=name)
    np.testing.assert_allclose(y_fused, y_ref, atol=REF_TOL * scale,
                               rtol=REF_TOL, err_msg=name)


def test_fused_parity_seeded_sweep():
    """Seeded-numpy randomized sweep (the no-hypothesis property test)."""
    for seed in range(8):
        rng = np.random.default_rng(1000 + seed)
        c_out = int(rng.integers(3, 70))
        c_in = int(rng.integers(3, 90))
        tile_n = int(2 ** rng.integers(2, 6))
        bits = rng.choice([2, 4, 8], size=c_out)
        _, qt = _mk_qtensor(seed, c_out, c_in, bits, tile_n)
        x = _x(seed + 99, int(rng.integers(1, 40)), c_in)
        y_fused = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
        y_pg = np.asarray(qt.matmul(x, jnp.float32,
                                    backend="pallas-pergroup"))
        y_jnp = np.asarray(qt.matmul(x, jnp.float32, backend="jnp"))
        np.testing.assert_array_equal(y_fused, y_pg, err_msg=f"seed={seed}")
        scale = max(1.0, np.abs(y_jnp).max())
        np.testing.assert_allclose(y_fused, y_jnp, atol=REF_TOL * scale,
                                   rtol=REF_TOL, err_msg=f"seed={seed}")


def test_fused_perm_folds_into_walk_order_for_single_group():
    """Single-precision weights need no output gather at all: the restore
    is the kernel's identity output index map (tile walk order)."""
    for bits_val, c_out, tile_n in [(4, 20, 8), (8, 129, 128), (2, 8, 8)]:
        _, qt = _mk_qtensor(3, c_out, 16, np.full(c_out, bits_val), tile_n)
        assert qt.fused_perm is None, (bits_val, c_out, tile_n)
        x = _x(5, 4, 16)
        np.testing.assert_array_equal(
            np.asarray(qt.matmul(x, jnp.float32, backend="pallas")),
            np.asarray(qt.matmul(x, jnp.float32, backend="pallas-pergroup")))


def test_fused_layout_skipped_for_deep_contractions():
    """K beyond the single-step budget keeps the per-group layout (the
    fused kernel reduces K in one dot) — backend="pallas" still works."""
    from repro.kernels import quant_matmul as qmk
    c_in = qmk.K_SINGLE_STEP_MAX + 4
    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, c_in)).astype(np.float32)
    qt = QTensor.from_assignment(w, np.full(8, 8), np.abs(w).max(-1),
                                 tile_n=8)
    assert qt.fused_packed is None and qt.tile_n is None
    x = _x(1, 2, c_in)
    y = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
    y_jnp = np.asarray(qt.matmul(x, jnp.float32, backend="jnp"))
    scale = max(1.0, np.abs(y_jnp).max())
    np.testing.assert_allclose(y, y_jnp, atol=1e-4 * scale, rtol=1e-4)


# ---------------------------------------------------------------------------
# End-to-end over the paper's four MLPerf-Tiny configs
# ---------------------------------------------------------------------------

TINY = ("resnet8-cifar10", "dscnn-kws", "mobilenetv1-vww", "dae-ad")


def _deployed_engine(name, seed=0, batch_size=2):
    cfg = tinyml.TINY_CONFIGS[name]
    eng = Engine.for_tinyml(cfg, key=jax.random.PRNGKey(seed))
    eng.randomize_nas(seed)
    eng.deploy(align=1)                  # tile_n="auto": fused layout
    batch = next(iter(pipe.SyntheticTiny(cfg, n=2 * batch_size,
                                         seed=seed).batches(batch_size)))
    return cfg, eng, batch


@pytest.mark.parametrize("name", TINY)
def test_tinyml_fused_bitexact_with_pergroup_and_matches_frozen(name):
    """Acceptance: fused single-launch serve == per-group serve bit-exactly
    and matches the frozen fake-quant reference on every MLPerf-Tiny
    config (depthwise sites take the identical grouped fall-back on both
    backends, so e2e equality covers every layer kind)."""
    _, eng, batch = _deployed_engine(name)
    frozen = np.asarray(
        eng.apply_fn(eng.params, eng.nas, PrecisionPolicy.FROZEN, batch),
        np.float32)
    out_fused = np.asarray(eng.serve(batch, backend="pallas"), np.float32)
    out_pg = np.asarray(eng.serve(batch, backend="pallas-pergroup"),
                        np.float32)
    np.testing.assert_array_equal(out_fused, out_pg,
                                  err_msg=f"{name}: fused vs per-group")
    scale = max(1.0, np.abs(frozen).max())
    np.testing.assert_allclose(out_fused, frozen, atol=1e-4 * scale,
                               rtol=1e-4, err_msg=f"{name}: fused vs frozen")


# ---------------------------------------------------------------------------
# Launch-count guards: exactly ONE pallas_call per deployed linear/conv
# ---------------------------------------------------------------------------

def _qtensor_sites(deployed_params):
    return {name: p["w"] for name, p in deployed_params.items()
            if isinstance(p, dict) and isinstance(p.get("w"), QTensor)}


def test_resnet8_serve_is_one_launch_per_layer():
    """The guard against silently falling back to the per-group loop: a
    deployed resnet8 forward must issue exactly one pallas_call per
    qlinear/qconv site (counted in the traced jaxpr — robust against jit
    caching), while the per-group backend issues one per precision group."""
    _, eng, batch = _deployed_engine("resnet8-cifar10")
    sites = _qtensor_sites(eng.deployed_params)
    n_sites = len(sites)
    n_groups = sum(len(qt.bits) for qt in sites.values())
    assert n_sites == 10                    # 8 backbone convs + shortcut...
    assert n_groups > n_sites               # randomized NAS => real mix

    def fwd(backend):
        pol = PrecisionPolicy.deployed(backend)
        return lambda dp, b: eng.apply_fn(dp, None, pol, b)

    assert ops.count_pallas_launches(fwd("pallas"), eng.deployed_params,
                                     batch) == n_sites
    assert ops.count_pallas_launches(fwd("pallas-pergroup"),
                                     eng.deployed_params, batch) == n_groups
    assert ops.count_pallas_launches(fwd("jnp"), eng.deployed_params,
                                     batch) == 0


def test_fused_matmul_is_single_pallas_call(monkeypatch):
    """Same guard at the QTensor level via a counting wrapper around
    ``pl.pallas_call`` (caches cleared so the trace really runs)."""
    from jax.experimental import pallas as pl

    _, qt = _mk_qtensor(7, 24, 32, np.asarray([2] * 8 + [4] * 8 + [8] * 8),
                        8)
    assert len(qt.bits) == 3
    x = _x(2, 4, 32)
    calls = []
    real = pl.pallas_call

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    ops.quant_matmul_fused.clear_cache()
    ops.quant_matmul.clear_cache()
    monkeypatch.setattr(pl, "pallas_call", counting)
    qt.matmul(x, jnp.float32, backend="pallas")
    assert len(calls) == 1                   # one launch, three precisions
    calls.clear()
    qt.matmul(x, jnp.float32, backend="pallas-pergroup")
    assert len(calls) == 3


# ---------------------------------------------------------------------------
# out_dtype default unification (ops.qtensor_matmul vs ops.qtensor_conv2d)
# ---------------------------------------------------------------------------

def test_qtensor_ops_default_out_dtype_is_f32():
    """Regression: qtensor_matmul defaulted to bf16 while qtensor_conv2d
    defaulted to f32 — both are f32 now (the bit-parity compute path)."""
    rng = np.random.default_rng(21)
    w = rng.standard_normal((12, 16)).astype(np.float32)
    qt = QTensor.from_assignment(w, rng.choice([2, 4, 8], size=12),
                                 np.abs(w).max(-1), tile_n=8)
    y = ops.qtensor_matmul(_x(1, 3, 16), qt)
    assert y.dtype == jnp.float32

    wc = rng.standard_normal((10, 4, 3, 3)).astype(np.float32)
    qtc = QTensor.from_assignment(wc, rng.choice([2, 4, 8], size=10),
                                  np.abs(wc.reshape(10, -1)).max(-1),
                                  tile_n=8)
    xc = jnp.asarray(rng.standard_normal((1, 6, 6, 4)), jnp.float32)
    yc = ops.qtensor_conv2d(xc, qtc)
    assert yc.dtype == jnp.float32
    # and both defaults agree numerically with the explicit f32 call
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(qt.matmul(_x(1, 3, 16), jnp.float32,
                                            backend="pallas")))
