"""The repro.api surface: QTensor pytree semantics, PrecisionPolicy
dispatch, and the Engine facade's search -> finetune -> deploy -> serve
lifecycle (acceptance: deployed model under jit through the Pallas
quant_matmul path == frozen fake-quant reference)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, Phase, PrecisionPolicy, QTensor, as_policy
from repro.core import mixedprec as mp
from repro.core import search
from repro.data import pipeline as pipe
from repro.models import layers as L
from repro.models import tinyml

CFG = mp.MixedPrecConfig()


def _qtensor(key=0, c_out=24, c_in=32, align=1):
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(key),
                                     (c_out, c_in)), np.float32)
    rng = np.random.default_rng(key)
    bits = rng.choice([2, 4, 8], size=c_out)
    alpha = np.abs(w).max(-1)
    qt = QTensor.from_assignment(w, bits, alpha, align=align)
    return w, bits, alpha, qt


# ---------------------------------------------------------------------------
# QTensor
# ---------------------------------------------------------------------------

def test_qtensor_is_registered_pytree():
    _, _, _, qt = _qtensor()
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    assert all(hasattr(l, "shape") for l in leaves)
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert qt2.bits == qt.bits and qt2.c_out == qt.c_out
    np.testing.assert_array_equal(np.asarray(qt2.inv_perm),
                                  np.asarray(qt.inv_perm))


def test_qtensor_flows_through_jit_and_vmap():
    w, _, _, qt = _qtensor()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))

    y_jit = jax.jit(lambda q, x: q.matmul(x))(qt, x)
    np.testing.assert_allclose(np.asarray(y_jit),
                               np.asarray(qt.matmul(x)), atol=1e-6)

    # vmap over a stacked QTensor (leading axis on every leaf)
    stacked = jax.tree_util.tree_map(
        lambda t: jnp.stack([t, t]), qt)
    xb = jnp.stack([x, x])
    yb = jax.vmap(lambda q, x: q.matmul(x))(stacked, xb)
    assert yb.shape == (2, 4, qt.c_out)
    np.testing.assert_allclose(np.asarray(yb[0]), np.asarray(y_jit),
                               atol=1e-6)


def test_qtensor_dequantize_matches_frozen_reference():
    w, bits, alpha, qt = _qtensor()
    gamma = np.zeros((w.shape[0], 3), np.float32)
    for i, b in enumerate(bits):
        gamma[i, {2: 0, 4: 1, 8: 2}[b]] = 9.0
    frozen = mp.frozen_weight(jnp.asarray(w), jnp.asarray(gamma),
                              jnp.asarray(alpha), CFG)
    np.testing.assert_allclose(np.asarray(qt.dequantize()),
                               np.asarray(frozen), atol=1e-5)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_qtensor_matmul_backends_agree(backend):
    _, _, _, qt = _qtensor(c_out=40, c_in=64)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 64))
    y = qt.matmul(x, jnp.float32, backend)
    y_ref = x @ qt.dequantize().T
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_qtensor_memory_and_group_sizes():
    _, bits, _, qt = _qtensor()
    assert sum(qt.group_sizes.values()) == qt.c_out
    for b, n in qt.group_sizes.items():
        assert n == int(np.sum(bits == b))
    assert qt.memory_bits == sum(int(p.size) * 8 for p in qt.packed)


def test_qtensor_conv_kernel_shape_roundtrip():
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (8, 3, 3, 3)),
                   np.float32)
    alpha = np.abs(w.reshape(8, -1)).max(-1)
    qt = QTensor.from_assignment(w, np.full(8, 8), alpha)
    assert qt.kernel_shape == (3, 3, 3)
    dense = qt.dense()
    assert dense.shape == w.shape
    # the symmetric 8-bit quantizer's guarantee: every weight within half a
    # step, alpha / (2 * 127), of its channel's grid (plus f32 roundoff)
    half_step = (alpha / (2 * 127))[:, None, None, None]
    err = np.abs(np.asarray(dense) - w)
    assert (err <= half_step + np.finfo(np.float32).eps * np.abs(w)).all(), \
        (err / half_step).max()


# ---------------------------------------------------------------------------
# PrecisionPolicy
# ---------------------------------------------------------------------------

def test_policy_singletons_and_pytree():
    assert PrecisionPolicy.FLOAT.phase is Phase.FLOAT
    assert not PrecisionPolicy.FLOAT.needs_nas
    assert PrecisionPolicy.FROZEN.needs_nas
    pol = PrecisionPolicy.search(3.3)
    leaves, treedef = jax.tree_util.tree_flatten(pol)
    assert len(leaves) == 1 and float(leaves[0]) == pytest.approx(3.3)
    pol2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert pol2.phase is Phase.SEARCH

    # tau is a LEAF: annealing it must not change the treedef (no retrace)
    _, td1 = jax.tree_util.tree_flatten(PrecisionPolicy.search(5.0))
    _, td2 = jax.tree_util.tree_flatten(PrecisionPolicy.search(4.9))
    assert td1 == td2


def test_as_policy_coercion():
    assert as_policy("float") is not None
    assert as_policy("qat8").phase is Phase.QAT8
    assert as_policy("search", tau=2.0).phase is Phase.SEARCH
    with pytest.raises(ValueError):
        as_policy("search")
    with pytest.raises(ValueError):
        as_policy("int3")
    p = PrecisionPolicy.FROZEN
    assert as_policy(p) is p


def test_qlinear_dispatches_on_policy_and_leaf_type():
    key = jax.random.PRNGKey(0)
    p = L.linear_init(key, 16, 8)
    nas = L.nas_init(key, 8, CFG)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16))
    y_float = L.qlinear(x, p, None, PrecisionPolicy.FLOAT, CFG)
    y_frozen = L.qlinear(x, p, nas, PrecisionPolicy.FROZEN, CFG)
    assert y_float.shape == y_frozen.shape == (4, 8)
    assert not np.allclose(np.asarray(y_float), np.asarray(y_frozen))
    # DEPLOYED policy over a float leaf is a type error, not silent fallback
    with pytest.raises(TypeError):
        L.qlinear(x, p, None, PrecisionPolicy.DEPLOYED, CFG)


# ---------------------------------------------------------------------------
# Engine facade (acceptance criterion)
# ---------------------------------------------------------------------------

def _engine(task="dae-ad", n=48, seed=0):
    cfg = tinyml.TINY_CONFIGS[task]
    settings = search.SearchSettings(
        cfg=cfg.quant, objective="size", lam=1e-6,
        warmup_epochs=1, search_epochs=1, finetune_epochs=1)
    eng = Engine.for_tinyml(cfg, settings, key=jax.random.PRNGKey(seed))
    data = pipe.SyntheticTiny(cfg, n=n, seed=seed)
    return cfg, eng, data


def test_engine_deployed_serve_matches_frozen_reference():
    """engine.deploy output runs under jax.jit end-to-end through the Pallas
    quant_matmul path and matches the frozen fake-quant reference."""
    cfg, eng, data = _engine()
    epochs = lambda: data.batches(16)
    eng.search(epochs).finetune(epochs)
    eng.deploy(align=1)
    batch = next(iter(data.batches(16, seed=5)))
    served = eng.serve(batch, backend="pallas")
    frozen = eng.apply_fn(eng.params, eng.nas, PrecisionPolicy.FROZEN, batch)
    np.testing.assert_allclose(np.asarray(served), np.asarray(frozen),
                               rtol=1e-3, atol=1e-3)
    # deployed leaves really are QTensors; packed model is smaller than f32
    site = sorted(eng.nas)[0]
    assert isinstance(eng.deployed_params[site]["w"], QTensor)
    assert eng.memory_bits() < 32 * sum(
        s.c_out * s.weights_per_channel for s in eng.specs.values())


def test_engine_deploy_alignment_promotion():
    """align=128 deployment still matches (promotion only adds precision)."""
    cfg, eng, data = _engine(n=32)
    epochs = lambda: data.batches(16)
    eng.search(epochs)
    eng.deploy(align=128)
    batch = next(iter(data.batches(16, seed=5)))
    served = eng.serve(batch, backend="jnp")
    assert bool(jnp.all(jnp.isfinite(served)))
    for name in eng.nas:
        qt = eng.deployed_params[name]["w"]
        sizes = qt.group_sizes
        for b, nrows in list(sizes.items())[:-1]:
            assert nrows % min(128, qt.c_out) == 0


def test_engine_history_phases():
    cfg, eng, data = _engine(n=32)
    epochs = lambda: data.batches(16)
    eng.search(epochs).finetune(epochs)
    phases = [h["phase"] for h in eng.history]
    assert "warmup" in phases and "search" in phases and "finetune" in phases
