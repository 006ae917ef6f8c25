"""Unit + property tests for core/quantizers.py (Eq. 1, PACT, packing).

Property-style sweeps use seeded numpy RNGs (deterministic, no external
dependencies); the pack/unpack round-trips are exhaustive over the value
range of every sub-byte width.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantizers as qz

BITS = (2, 4, 8)


# ---------------------------------------------------------------------------
# Fake-quantization properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", BITS)
def test_act_quant_levels(bits):
    """Quantized activations take at most 2^bits distinct values in [0, a]."""
    x = jnp.linspace(-1.0, 8.0, 1001)
    y = qz.quantize_act(x, jnp.asarray(6.0), bits)
    vals = np.unique(np.asarray(y))
    assert len(vals) <= (1 << bits)
    assert vals.min() >= 0.0 and vals.max() <= 6.0 + 1e-6


@pytest.mark.parametrize("bits", BITS)
def test_weight_quant_symmetric(bits):
    """Signed weight quantization: symmetric levels, zero representable."""
    w = jnp.linspace(-2.0, 2.0, 1001)
    y = qz.quantize_weight(w, jnp.asarray(1.5), bits)
    vals = np.unique(np.asarray(y))
    assert len(vals) <= (1 << bits) - 1 or bits == 8
    np.testing.assert_allclose(vals, -vals[::-1], atol=1e-6)  # symmetric
    assert 0.0 in np.round(vals, 6)


def test_8bit_quant_near_identity():
    x = jnp.linspace(0.01, 5.99, 100)
    y = qz.quantize_act(x, jnp.asarray(6.0), 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=6 / 255)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("bits", BITS)
def test_quant_error_bounded(seed, bits):
    """|fq(x) - clip(x)| <= step/2 — the core quantization invariant."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(64) * 3, jnp.float32)
    alpha = 2.0
    y = qz.quantize_act(x, jnp.asarray(alpha), bits)
    clipped = np.clip(np.asarray(x), 0, alpha)
    step = alpha / ((1 << bits) - 1)
    assert np.max(np.abs(np.asarray(y) - clipped)) <= step / 2 + 1e-6


def test_ste_gradient_passthrough():
    """d/dx fq(x) == 1 inside the clip range, 0 outside."""
    g = jax.grad(lambda x: qz.quantize_act(x, jnp.asarray(6.0), 4))
    assert g(jnp.asarray(3.0)) == 1.0
    assert g(jnp.asarray(7.0)) == 0.0
    assert g(jnp.asarray(-1.0)) == 0.0


def test_pact_alpha_gradient():
    """PACT: d fq/d alpha == 1 for saturated inputs, ~0 for interior."""
    g = jax.grad(lambda a: qz.quantize_act(jnp.asarray(10.0), a, 4))
    assert abs(float(g(jnp.asarray(6.0))) - 1.0) < 1e-5
    g_in = jax.grad(lambda a: qz.quantize_act(jnp.asarray(1.5), a, 8))
    assert abs(float(g_in(jnp.asarray(6.0)))) < 0.1


# ---------------------------------------------------------------------------
# Integer quantization + sub-byte packing roundtrips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("bits", BITS)
# k=1040 spans several PACK_CHUNK byte chunks plus a short tail chunk
@pytest.mark.parametrize("k", [8, 16, 64, 256, 1040])
def test_pack_unpack_roundtrip(seed, bits, k):
    rng = np.random.default_rng(seed * 97 + bits)
    half = (1 << (bits - 1)) - 1
    q = jnp.asarray(rng.integers(-half, half + 1, (4, k)), jnp.int8)
    packed = qz.pack_int(q, bits)
    assert packed.shape == (4, k * bits // 8)
    assert packed.dtype == jnp.uint8
    out = qz.unpack_int(packed, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(q))


@pytest.mark.parametrize("bits", (2, 4))
def test_pack_unpack_exhaustive_value_range(bits):
    """Every representable signed value round-trips — including the most
    negative two's-complement code (-2^(bits-1)), which the symmetric
    quantizer never emits but the packing layer must still carry."""
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    f = qz.pack_factor(bits)
    vals = np.arange(lo, hi + 1, dtype=np.int8)
    # all values in every lane position of a byte
    tiled = np.tile(vals, f)[None, :]                  # (1, n_vals * f)
    q = jnp.asarray(tiled)
    out = qz.unpack_int(qz.pack_int(q, bits), bits)
    np.testing.assert_array_equal(np.asarray(out), tiled)


def test_pack_unpack_int8_negative_values():
    q = jnp.asarray([[-128, -1, 0, 1, 127]], jnp.int8)
    packed = qz.pack_int(q, 8)
    assert packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(qz.unpack_int(packed, 8)),
                                  np.asarray(q))


@pytest.mark.parametrize("bits", BITS)
def test_pack_unpack_non_contiguous_input(bits):
    """Transposed / sliced (non-contiguous) inputs pack identically to their
    contiguous copies."""
    rng = np.random.default_rng(3)
    half = (1 << (bits - 1)) - 1
    base = rng.integers(-half, half + 1, (32, 64)).astype(np.int8)
    view = base.T[::2]                                  # (32, 32), strided
    assert not view.flags["C_CONTIGUOUS"]
    p_view = qz.pack_int(jnp.asarray(view), bits)
    p_copy = qz.pack_int(jnp.asarray(np.ascontiguousarray(view)), bits)
    np.testing.assert_array_equal(np.asarray(p_view), np.asarray(p_copy))
    np.testing.assert_array_equal(
        np.asarray(qz.unpack_int(p_view, bits)), view)


@pytest.mark.parametrize("bits", (2, 4))
def test_pack_unpack_higher_rank(bits):
    """Leading batch/expert dims pass through packing untouched."""
    rng = np.random.default_rng(11)
    half = (1 << (bits - 1)) - 1
    q = jnp.asarray(rng.integers(-half, half + 1, (3, 5, 16)), jnp.int8)
    packed = qz.pack_int(q, bits)
    assert packed.shape == (3, 5, 16 * bits // 8)
    np.testing.assert_array_equal(np.asarray(qz.unpack_int(packed, bits)),
                                  np.asarray(q))


@pytest.mark.parametrize("bits", BITS)
def test_int_quant_dequant_error(bits):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    alpha = jnp.max(jnp.abs(w), axis=-1, keepdims=True)
    q, scale = qz.quantize_weight_int(w, alpha, bits)
    back = np.asarray(q, np.float32) * np.asarray(scale)
    step = np.asarray(alpha) / ((1 << (bits - 1)) - 1)
    assert np.max(np.abs(back - np.asarray(w)) / step) <= 0.5 + 1e-5


def test_weight_bank_shapes():
    w = jnp.ones((8, 4))
    bank = qz.weight_bank(w, jnp.ones((8, 1)))
    assert bank.shape == (3, 8, 4)
