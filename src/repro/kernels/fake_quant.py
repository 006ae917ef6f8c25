"""Pallas TPU kernel: fused DNAS weight mixture (Eq. 5) in one HBM pass.

The search-phase forward fake-quantizes every weight at |P_W| precisions and
mixes them (core/mixedprec.effective_weight).  Naively that reads W from HBM
once and writes |P_W| temporaries + the mixture — 4x the weight traffic of a
plain forward.  This kernel computes

    out[n, k] = sum_p gamma_hat[n, p] * FQ(w[n, k]; alpha[n], p)

in a single pass: one W read, one OUT write, everything else in VMEM.  This
is the "fused fake-quant" beyond-paper optimization (it attacks the memory
roofline term of the search phase).

Grid (N/bn, K/bk); blocks: w (bn, bk), gamma_hat (bn, P), alpha (bn, 1).
The P loop is unrolled (|P_W| = 3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(w_ref, g_ref, a_ref, o_ref, *, bitwidths: tuple[int, ...]):
    w = w_ref[...].astype(jnp.float32)                    # (bn, bk)
    a = jnp.maximum(a_ref[...].astype(jnp.float32), 1e-6)  # (bn, 1)
    acc = jnp.zeros_like(w)
    for i, bits in enumerate(bitwidths):
        half = (1 << (bits - 1)) - 1
        step = a / half
        q = jnp.clip(w, -a, a) / step
        q = jnp.round(q) * step
        acc = acc + g_ref[...][:, i:i + 1].astype(jnp.float32) * q
    o_ref[...] = acc


def fused_mix_2d(w: jnp.ndarray, gamma_hat: jnp.ndarray, alpha: jnp.ndarray,
                 bitwidths=(2, 4, 8), *, bn: int = 256, bk: int = 512,
                 interpret: bool | None = None) -> jnp.ndarray:
    """w (N, K), gamma_hat (N, |P|), alpha (N,) -> mixed weights (N, K) f32.

    Forward-only fused path (the VJP falls back to the reference expression —
    the mixture is linear in gamma_hat and piecewise-linear in w, so training
    uses mixedprec.effective_weight; serving/eval and the frozen fine-tune
    phase use this kernel).
    """
    N, K = w.shape
    bn, bk = min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    kern = functools.partial(_kernel, bitwidths=tuple(bitwidths))
    return pl.pallas_call(
        kern,
        grid=(N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bn, len(bitwidths)), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        interpret=interpret_mode(interpret),
    )(w, gamma_hat, alpha.reshape(N, 1))
