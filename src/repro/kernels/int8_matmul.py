"""Pallas TPU kernel: dynamic int8 x int8 -> int32 GEMM for *training*.

The training-compute counterpart of ``quant_matmul.py`` (which serves packed
*static* weights): both operands are quantized **dynamically per row** of
their contraction axis — symmetric, absmax-scaled, the gau-nernst/quant-train
recipe — multiplied on the MXU as int8 with int32 accumulation, and
dequantized in a fused epilogue:

    y[m, n] = (sum_k a_i8[m, k] * b_i8[n, k]) * sa[m] * sb[n]

Because int32 accumulation of int8 products is exact (no rounding anywhere
in the reduction), the kernel's output is **bitwise identical** to the jnp
reference :func:`scaled_int8_mm_ref` for any K schedule — the float epilogue
multiplies in one fixed order (acc * sa then * sb).  That is the acceptance
contract the forward path of ``repro.qtrain`` tests against, and it also
means zero-padding M/N/K to tile multiples is exact, not approximate.

Quantization (:func:`rowwise_quantize`) supports two rounding modes:

* deterministic round-to-nearest (``key=None``) — the forward pass;
* **stochastic rounding** (``key`` given) — ``floor(x/s + u)``,
  ``u ~ U[0, 1)``: unbiased (``E[q] = x/s``), exact on already-representable
  values, deterministic per PRNG key.  The backward matmuls use this so the
  quantization noise of ``dy``/``x``/``w`` does not bias the gradient
  estimate across steps (Schaefer et al., 2206.07741).

The SR uniforms come from ``jax.random`` *outside* the kernel: the TPU
in-kernel PRNG (``pltpu.prng_random_bits``) has no interpret-mode
implementation, and quantization is bandwidth-trivial next to the GEMM.

Tiling: grid (M/bm, N/bn); K is not gridded — training GEMMs here contract
at most a few thousand columns, one MXU dot each (same single-K-step
rationale as ``quant_matmul.K_SINGLE_STEP_MAX``).  int32 overflow needs
``K * 127 * 127 < 2^31`` i.e. K < ~133k, far above any model dimension in
this repo; guarded with an explicit error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

# K ceiling for exact int32 accumulation: K * 127 * 127 <= 2^31 - 1.
K_INT32_EXACT_MAX = (2 ** 31 - 1) // (127 * 127)

_DIM_NUMS = (((1,), (1,)), ((), ()))    # contract last axis of both operands


def rowwise_quantize(x: jnp.ndarray, key=None):
    """Symmetric per-row int8 quantization over the last axis.

    ``x (..., K) -> (q int8 (..., K), scale f32 (...,))`` with
    ``scale = max(|row|) / 127`` (floored at 1e-6/127, matching the
    quantizer epsilon used everywhere else in this repo).  ``key=None``
    rounds to nearest; with a PRNG key the round is stochastic:
    ``floor(x/s + u)``, unbiased and exact on representable values.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    y = x32 / scale[..., None]
    if key is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + jax.random.uniform(key, x.shape, jnp.float32))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def scaled_int8_mm_ref(a: jnp.ndarray, b: jnp.ndarray, sa: jnp.ndarray,
                       sb: jnp.ndarray) -> jnp.ndarray:
    """jnp reference for :func:`scaled_int8_mm` — bitwise identical to the
    kernel (exact int32 reduction; epilogue multiplies in the same order)."""
    acc = jax.lax.dot_general(a, b, _DIM_NUMS,
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa.astype(jnp.float32)[:, None] \
        * sb.astype(jnp.float32)[None, :]


def _int8_mm_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref):
    acc = jax.lax.dot_general(a_ref[...], b_ref[...], _DIM_NUMS,
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * sa_ref[...]             # (bm, 1)
    o_ref[...] = out * sb_ref[...]                          # (1, bn)


def _pad_axis(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_tile(n: int, t: int) -> int:
    """Shrink a tile to the next pow2 >= n for small dims (same policy as
    ``ops._pick_bm`` so tiny training batches do not pad to 128)."""
    return min(t, max(8, 1 << (n - 1).bit_length())) if n < t else t


@functools.partial(jax.jit,
                   static_argnames=("backend", "bm", "bn", "interpret"))
def scaled_int8_mm(a: jnp.ndarray, b: jnp.ndarray, sa: jnp.ndarray,
                   sb: jnp.ndarray, backend: str = "pallas",
                   bm: int = 128, bn: int = 128,
                   interpret=None) -> jnp.ndarray:
    """``a_i8 (M, K) @ b_i8 (N, K)^T * sa[:, None] * sb[None, :] -> f32``.

    The int8 training GEMM with the dequant epilogue fused into the kernel.
    ``backend="jnp"`` runs the (bitwise-identical) reference — used under
    vmap and as the CI cross-check.  ``interpret=None`` lets
    :func:`repro.kernels.interpret_mode` decide from the platform.
    """
    M, K = a.shape
    N = b.shape[0]
    if K != b.shape[1]:
        raise ValueError(f"contraction mismatch: a {a.shape} vs b {b.shape}")
    if K > K_INT32_EXACT_MAX:
        raise ValueError(
            f"K={K} overflows exact int32 accumulation "
            f"(max {K_INT32_EXACT_MAX}); shard the contraction first")
    if backend == "jnp":
        return scaled_int8_mm_ref(a, b, sa, sb)
    bm_, bn_ = _pick_tile(M, bm), _pick_tile(N, bn)
    # zero padding is exact: padded rows/cols accumulate zeros and their
    # (zero) scales make the epilogue a no-op; K pads to the MXU lane width
    ap = _pad_axis(_pad_axis(a, 0, bm_), 1, 128)
    bp = _pad_axis(_pad_axis(b, 0, bn_), 1, 128)
    sap = _pad_axis(sa.astype(jnp.float32), 0, bm_)
    sbp = _pad_axis(sb.astype(jnp.float32), 0, bn_)
    Mp, Kp = ap.shape
    Np = bp.shape[0]
    out = pl.pallas_call(
        _int8_mm_kernel,
        grid=(Mp // bm_, Np // bn_),
        in_specs=[
            pl.BlockSpec((bm_, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, Kp), lambda i, j: (j, 0)),
            pl.BlockSpec((bm_, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn_), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        interpret=interpret_mode(interpret),
    )(ap, bp, sap.reshape(Mp, 1), sbp.reshape(1, Np))
    return out[:M, :N]


def int8_matmul(a: jnp.ndarray, b: jnp.ndarray, key_a=None, key_b=None,
                backend: str = "pallas") -> jnp.ndarray:
    """Quantize-then-multiply convenience: float ``a (M, K)`` x ``b (N, K)``
    -> f32 ``(M, N)`` through dynamic per-row int8.  ``key_a``/``key_b``
    switch the respective operand's quantize to stochastic rounding."""
    qa, sa = rowwise_quantize(a, key_a)
    qb, sb = rowwise_quantize(b, key_b)
    return scaled_int8_mm(qa, qb, sa, sb, backend=backend)
