"""Pallas decode-attention kernel over the packed channel-wise KV cache.

The cache analog of the fused expert GEMM's ``dequant_first`` contract
(kernels/quant_matmul.py): the K/V rings are stored as packed sub-byte bytes
(models/kv_quant.py — contiguous channel groups at 2/4/8 bits, one scale per
token per group) and this kernel unpacks + scales each tile **in VMEM**
right before the dot, so HBM cache traffic stays the packed bytes.  One
``pallas_call`` serves the whole one-token GQA decode attention:

    grid (B, KV): block ``(b, g)`` loads query rows ``q[b, g*rep:(g+1)*rep]``
    (the GQA head group sharing kv-head ``g`` — no materialized
    ``jnp.repeat``), the packed K/V rings ``(S, packed_bytes)`` and scales
    ``(S, n_groups)`` of that kv head, dequantizes in VMEM, and computes
    masked softmax attention over positions ``<= pos[b]``.

The arithmetic mirrors ``models/attention.gqa_decode``'s jnp reference op
for op (bf16 score dot -> f32 mask/softmax -> bf16 value dot), so the fused
path produces the same tokens as the jnp packed path and — at 8-bit — as
the legacy int8 engine (the bit-parity harness in tests/test_kv_quant.py).

Callers pass GATHERED per-slot ring views: the paged engine's page gather
(cache/paged.gather_pages) is a pure index copy of packed bytes, so pages
stream packed end to end and the kernel is oblivious to the page table —
the same composition contract as PR 6's dense-ring equivalence.

Static parameters are plain ``(bits, sizes)`` tuples rather than the
KVQuantSpec object so the kernels layer stays import-independent of
``repro.models``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantizers as qz
from repro.kernels import interpret_mode


def _dequant_tile(packed, scales, bits, sizes, dtype):
    """In-VMEM dequant of one ring tile: ``(S, packed_bytes)`` -> ``(S, feat)``.

    Elementwise-identical to ``models.kv_quant.dequant_channelwise`` (unpack
    -> f32 -> per-group scale -> cast), so the fused and jnp paths agree.
    """
    outs, lo = [], 0
    for g, (b, n) in enumerate(zip(bits, sizes)):
        nb = n * b // 8
        q = qz.unpack_int(packed[:, lo:lo + nb], b)
        lo += nb
        outs.append((q.astype(jnp.float32)
                     * scales[:, g:g + 1]).astype(dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def _kernel(pos_ref, q_ref, kp_ref, ks_ref, vp_ref, vs_ref, o_ref, *,
            bits, sizes, compute_dtype):
    q = q_ref[...]                           # (rep, hd) native dtype
    kf = _dequant_tile(kp_ref[...], ks_ref[...], bits, sizes,
                       compute_dtype)        # (S, hd)
    vf = _dequant_tile(vp_ref[...], vs_ref[...], bits, sizes,
                       compute_dtype)
    hd = kf.shape[1]
    # f32 scores straight from the f32 accumulator (the MXU needs one).
    # The reference einsum's bf16 scores are widened to f32 in the same
    # program, and XLA keeps them at the accumulator's precision (excess
    # precision), so both paths score alike for bf16 and f32 queries.
    s = jnp.dot(q, kf.T, preferred_element_type=jnp.float32
                ) / math.sqrt(hd)                               # (rep, S)
    valid = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
             <= pos_ref[pl.program_id(0)])
    s = jnp.where(valid, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
    o_ref[...] = jnp.dot(w, vf, preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "sizes", "out_dtype",
                                             "interpret"))
def decode_attention(q: jnp.ndarray, k_packed: jnp.ndarray,
                     k_scales: jnp.ndarray, v_packed: jnp.ndarray,
                     v_scales: jnp.ndarray, pos: jnp.ndarray,
                     bits: tuple, sizes: tuple, out_dtype=jnp.bfloat16,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Fused packed-cache GQA decode attention.

    ``q (B, KV, rep, hd)`` query head groups in their NATIVE dtype (f32
    after RoPE — the score dot then promotes exactly like the reference
    einsum, which is what keeps the fused path token-identical to jnp);
    ``k_packed``/``v_packed (B, KV, S, packed_bytes)`` uint8 ring views;
    ``k_scales``/``v_scales (B, KV, S, n_groups)`` f32; ``pos (B,)`` int32
    per-slot positions (attend to ``<= pos[b]``).  Returns
    ``(B, KV, rep, hd)`` in ``out_dtype``.
    """
    B, KV, rep, hd = q.shape
    S, NB = k_packed.shape[2], k_packed.shape[3]
    G = k_scales.shape[3]
    assert sum(sizes) == hd and sum(n * b // 8 for b, n in
                                    zip(bits, sizes)) == NB, (bits, sizes,
                                                              hd, NB)
    ring = lambda nf: pl.BlockSpec((None, None, S, nf),
                                   lambda b, g: (b, g, 0, 0))
    head = pl.BlockSpec((None, None, rep, hd), lambda b, g: (b, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, sizes=sizes,
                          compute_dtype=out_dtype),
        grid=(B, KV),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # pos, whole
                  head, ring(NB), ring(G), ring(NB), ring(G)],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, hd), out_dtype),
        interpret=interpret_mode(interpret),
        name="decode_attention",
    )(pos, q, k_packed, k_scales, v_packed, v_scales)
