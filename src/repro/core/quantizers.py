"""Quantizers: affine fake-quantization with straight-through estimators.

Implements the paper's Eq. (1) affine scheme and the PACT variant (learnable
clipping, Choi et al. 2018) used for both activations and weights, exactly as
the paper replaces EdMIPS' Gaussian quantizer with PACT (Sec. III-A).

All functions are pure and jit/vmap/scan friendly.  Gradients flow through the
round/clamp via the straight-through estimator (STE):

    fq(x) = x + stop_grad(q(x) - x)

For PACT the clip parameter ``alpha`` receives its analytic gradient (the
gradient of the clamp boundary), which falls out naturally from expressing the
clamp with ``jnp.clip`` *outside* the stop_gradient.

Conventions
-----------
* Activations are quantized **unsigned** on ``[0, alpha]`` (post-ReLU/GELU
  tensors; the affine zero-point is 0) — Eq. (1) with ``alpha_t = 0``.
* Weights are quantized **symmetric signed** on ``[-alpha, alpha]`` with
  ``2^n - 1`` levels (zero exactly representable).
* Per-channel weight quantization uses one ``alpha`` per output channel
  (axis 0 of the weight as stored ``(c_out, ...)`` — callers reshape).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

# Bit-widths supported by the search space (and by the MPIC deployment
# target of the paper).  Kept as a module constant so regularizers, the
# deploy transform and the Pallas kernels agree on ordering.
DEFAULT_BITWIDTHS: tuple[int, ...] = (2, 4, 8)


def _round_ste(x: jnp.ndarray) -> jnp.ndarray:
    """round() with a straight-through gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def quantize_act(x: jnp.ndarray, alpha: jnp.ndarray, bits: int) -> jnp.ndarray:
    """PACT fake-quantization for activations (unsigned, [0, alpha]).

    Eq. (1) of the paper with alpha_t = 0, eps = alpha / (2^n - 1).
    ``alpha`` is a learnable scalar (or broadcastable) clip value.
    """
    alpha = jnp.maximum(alpha, 1e-6)  # keep the step strictly positive
    levels = (1 << bits) - 1
    # clip participates in the alpha gradient; round is STE.
    y = jnp.clip(x, 0.0, alpha)
    step = alpha / levels
    return _round_ste(y / step) * step


def quantize_act_signed(x: jnp.ndarray, alpha: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Symmetric signed PACT for activations.

    The paper quantizes post-ReLU CNN activations unsigned; transformer hidden
    states are signed, so the LM-family archs use this variant (recorded as a
    hardware/domain adaptation in DESIGN.md).  Same STE/clip-gradient
    structure as :func:`quantize_act`.
    """
    alpha = jnp.maximum(alpha, 1e-6)
    half_levels = (1 << (bits - 1)) - 1
    y = jnp.clip(x, -alpha, alpha)
    step = alpha / half_levels
    return _round_ste(y / step) * step


def quantize_act_any(x: jnp.ndarray, alpha: jnp.ndarray, bits: int,
                     signed: bool) -> jnp.ndarray:
    return (quantize_act_signed if signed else quantize_act)(x, alpha, bits)


def quantize_weight(w: jnp.ndarray, alpha: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Symmetric signed PACT-style fake-quantization for weights.

    ``alpha`` broadcasts against ``w`` — pass shape ``(c_out, 1, ...)`` for
    per-channel clipping (the paper shares one float master tensor across all
    precisions; only the number of levels changes per ``bits``).
    """
    alpha = jnp.maximum(alpha, 1e-6)
    half_levels = (1 << (bits - 1)) - 1  # e.g. 127 for 8b, 7 for 4b, 1 for 2b
    y = jnp.clip(w, -alpha, alpha)
    step = alpha / half_levels
    return _round_ste(y / step) * step


def quantize_weight_int(w: jnp.ndarray, alpha: jnp.ndarray, bits: int
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """True integer quantization (deployment path, no STE).

    Returns ``(q, scale)`` with ``q`` int8-typed integers in
    ``[-half_levels, half_levels]`` and ``w ≈ q * scale``.
    """
    alpha = jnp.maximum(alpha, 1e-6)
    half_levels = (1 << (bits - 1)) - 1
    step = alpha / half_levels
    q = jnp.clip(jnp.round(w / step), -half_levels, half_levels).astype(jnp.int8)
    return q, step


def quantize_act_int(x: jnp.ndarray, alpha: jnp.ndarray, bits: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Unsigned integer activation quantization (deployment path)."""
    alpha = jnp.maximum(alpha, 1e-6)
    levels = (1 << bits) - 1
    step = alpha / levels
    q = jnp.clip(jnp.round(x / step), 0, levels).astype(jnp.uint8)
    return q, step


# ---------------------------------------------------------------------------
# Sub-byte packing.  TPU HBM is byte addressed; int4/int2 weights are stored
# packed into uint8 (2 resp. 4 values per byte) and unpacked in VMEM by the
# Pallas kernels (kernels/quant_matmul.py, kernels/decode_attention.py) with
# the same :func:`unpack_int` the jnp fallback uses.
# Packing is along the LAST axis, which must be divisible by the pack factor.
#
# Layout: the packed bytes are cut into chunks of PACK_CHUNK bytes (the last
# chunk may be shorter).  A chunk of ``w`` bytes holds ``w * f`` consecutive
# values as ``f`` planes: value ``j * w + b`` of the chunk sits in byte ``b``
# at bit ``j * bits``.  Unpacking is then shift/mask per plane and a lane
# concatenation: no lane interleave, which the TPU compiler cannot lower.
# A K block of a kernel grid that spans whole chunks is a contiguous byte
# block, so gridded kernels unpack their own block alone.
# ---------------------------------------------------------------------------

PACK_CHUNK = 128


def pack_factor(bits: int) -> int:
    assert bits in (2, 4, 8), bits
    return 8 // bits


def _chunks(nbytes: int):
    return [(c0, min(c0 + PACK_CHUNK, nbytes))
            for c0 in range(0, nbytes, PACK_CHUNK)]


def pack_int(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack signed integers (int8 storage, values fit in ``bits``) to uint8.

    Values are biased to unsigned (two's-complement within ``bits``) before
    packing so unpacking is branch-free.
    """
    if bits == 8:
        return q.astype(jnp.int8).view(jnp.uint8) if q.dtype != jnp.uint8 else q
    f = pack_factor(bits)
    assert q.shape[-1] % f == 0, (q.shape, bits)
    mask = (1 << bits) - 1
    u = (q.astype(jnp.int32) & mask).astype(jnp.uint8)
    out = []
    for c0, c1 in _chunks(q.shape[-1] // f):
        w = c1 - c0
        byte = jnp.zeros(q.shape[:-1] + (w,), jnp.uint8)
        for j in range(f):
            plane = u[..., c0 * f + j * w: c0 * f + (j + 1) * w]
            byte = byte | (plane << jnp.uint8(j * bits)).astype(jnp.uint8)
        out.append(byte)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)


def unpack_int(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack_int`; returns int8 values, last axis expanded.

    Written with ops the TPU compiler lowers (int32 shifts, static lane
    slices, lane concatenation), so the Pallas kernels call it on their
    VMEM blocks.
    """
    if bits == 8:
        return packed.view(jnp.int8)
    f = pack_factor(bits)
    mask = (1 << bits) - 1
    sign = 1 << (bits - 1)
    u = packed.astype(jnp.int32)
    planes = []
    for j in range(f):
        v = (u >> (j * bits)) & mask
        planes.append(jnp.where(v >= sign, v - (1 << bits), v))
    pieces = [p[..., c0:c1] for c0, c1 in _chunks(packed.shape[-1])
              for p in planes]
    return jnp.concatenate(pieces, axis=-1).astype(jnp.int8)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Fake-quant banks: the DNAS needs all |P| fake-quantized copies of a tensor
# at once (Eq. 4 / Eq. 5).  Generated on the fly from the single float master
# tensor (weight sharing — Sec. III-A).
# ---------------------------------------------------------------------------

def act_bank(x: jnp.ndarray, alpha: jnp.ndarray,
             bitwidths: Sequence[int] = DEFAULT_BITWIDTHS) -> jnp.ndarray:
    """Stack of fake-quantized activations, shape ``(|P_X|, *x.shape)``."""
    return jnp.stack([quantize_act(x, alpha, b) for b in bitwidths])


def weight_bank(w: jnp.ndarray, alpha: jnp.ndarray,
                bitwidths: Sequence[int] = DEFAULT_BITWIDTHS) -> jnp.ndarray:
    """Stack of fake-quantized weights, shape ``(|P_W|, *w.shape)``."""
    return jnp.stack([quantize_weight(w, alpha, b) for b in bitwidths])


def init_act_alpha() -> jnp.ndarray:
    """PACT initializes the activation clip around the expected dynamic range."""
    return jnp.asarray(6.0, dtype=jnp.float32)  # ReLU6-like prior


def init_weight_alpha(w: jnp.ndarray, per_channel: bool = True) -> jnp.ndarray:
    """Init weight clip to the per-channel max-abs (axis 0 = output channel)."""
    if per_channel:
        reduce_axes = tuple(range(1, w.ndim))
        a = jnp.max(jnp.abs(w), axis=reduce_axes)
        return jnp.maximum(a, 1e-3).astype(jnp.float32)
    return jnp.maximum(jnp.max(jnp.abs(w)), 1e-3).astype(jnp.float32)
