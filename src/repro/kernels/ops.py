"""jit'd public wrappers around the Pallas kernels: padding, batching,
backend selection.

``quant_matmul_fused`` is the deployed hot path: ONE ``pallas_call`` for a
whole multi-precision weight (tile-aligned fused layout, see
kernels/quant_matmul.py).  ``quant_matmul`` is the per-group reference path
(one launch per precision group — ``backend="pallas-pergroup"``) and what
legacy non-tile-aligned QTensors use.  Both accept arbitrary leading batch
dims and unpadded shapes, pad to tile multiples, invoke the kernel, and
slice back.
"""
from __future__ import annotations

import functools

import jax
import jax.extend.core
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quantizers as qz
from repro.kernels import fake_quant as fq_kernel
from repro.kernels import quant_conv as qc_kernel
from repro.kernels import quant_matmul as qm_kernel

def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("bits", "c_in", "out_dtype", "bm", "bn",
                                    "bk", "compute_dtype"))
def quant_matmul(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                 bits: int, c_in: int, out_dtype=jnp.bfloat16,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """x (..., c_in) @ dequant(packed (n, ceil(c_in/f))) -> (..., n)."""
    f = qz.pack_factor(bits)
    Kp = packed.shape[1] * f                     # pack-padded c_in
    if x.shape[-1] != c_in:
        raise ValueError(
            f"x contraction dim {x.shape[-1]} != c_in {c_in} — for conv "
            "patches this means the im2col width does not match the packed "
            "kernel's C*kh*kw")
    if not 0 <= Kp - c_in < f:
        raise ValueError(
            f"packed K {Kp} (= {packed.shape[1]} bytes * {f}) does not "
            f"correspond to c_in {c_in} at {bits} bits")
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, x.shape[-1]).astype(compute_dtype)
    N = packed.shape[0]
    x2 = _pad_to(x2, 1, Kp)                      # exactly Kp (single pad)
    # choose tile sizes that divide (pad where they don't)
    bm_ = _pick_bm(M, bm)
    x2 = _pad_to(x2, 0, bm_)
    packed_p = _pad_to(packed, 0, bn) if N % bn else packed
    scale_p = _pad_to(scale, 0, bn) if N % bn else scale
    bk_ = qm_kernel.pick_bk(Kp, f, bk)
    y = qm_kernel.quant_matmul_2d(x2, packed_p, scale_p, bits, bm=bm_,
                                  bn=min(bn, packed_p.shape[0]), bk=bk_,
                                  out_dtype=out_dtype,
                                  compute_dtype=compute_dtype)
    return y[:M, :N].reshape(*lead, N)


def _pick_bm(M: int, bm: int) -> int:
    """M tile size — shared by the per-group and fused entry points so the
    two paths pad M identically (part of the bit-exactness contract)."""
    return min(bm, max(8, 1 << (M - 1).bit_length())) if M < bm else bm


@functools.partial(jax.jit,
                   static_argnames=("tile_bits", "tile_n", "c_in", "c_out",
                                    "out_dtype", "bm", "compute_dtype"))
def quant_matmul_fused(x: jnp.ndarray, fused_packed: jnp.ndarray,
                       fused_scales: jnp.ndarray, fused_perm, tile_bits: tuple,
                       tile_n: int, c_in: int, c_out: int,
                       out_dtype=jnp.float32, bm: int = 128,
                       compute_dtype=jnp.float32) -> jnp.ndarray:
    """Whole multi-precision GEMM ``x (..., c_in) -> (..., c_out)`` in ONE
    kernel launch over the tile-aligned fused layout (kernels/quant_matmul).

    ``tile_bits`` is the static per-output-tile bit-width schedule (walk
    order), ``fused_packed`` the ragged byte buffer, ``fused_scales`` the
    per-channel steps in walk order.  ``fused_perm`` is ``None`` when the
    deploy transform folded the channel-order restore into the schedule's
    walk order (the output needs only the tail-padding slice); otherwise it
    gathers the ``c_out`` real columns into target order — a single take,
    with no per-group concat either way.
    """
    if x.shape[-1] != c_in:
        raise ValueError(
            f"x contraction dim {x.shape[-1]} != c_in {c_in} — for conv "
            "patches this means the im2col width does not match the packed "
            "kernel's C*kh*kw")
    Kp = -(-c_in // qm_kernel.FUSED_K_ALIGN) * qm_kernel.FUSED_K_ALIGN
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, c_in).astype(compute_dtype)
    x2 = _pad_to(x2, 1, Kp)
    bm_ = _pick_bm(M, bm)
    x2 = _pad_to(x2, 0, bm_)
    y = qm_kernel.quant_matmul_fused_2d(
        x2, fused_packed, fused_scales, tile_bits, Kp=Kp, tile_n=tile_n,
        bm=bm_, out_dtype=out_dtype,
        compute_dtype=compute_dtype)
    y = y[:M]
    if fused_perm is not None:
        y = jnp.take(y, fused_perm, axis=-1)
    else:
        y = y[:, :c_out]
    return y.reshape(*lead, c_out)


@functools.partial(jax.jit,
                   static_argnames=("tile_bits", "tile_n", "c_in", "c_out",
                                    "out_dtype", "bm", "compute_dtype"))
def quant_matmul_fused_batched(x: jnp.ndarray, fused_packed: jnp.ndarray,
                               fused_scales: jnp.ndarray, fused_perm,
                               tile_bits: tuple, tile_n: int, c_in: int,
                               c_out: int, out_dtype=jnp.float32,
                               bm: int = 128,
                               compute_dtype=jnp.float32) -> jnp.ndarray:
    """Expert-stacked fused GEMM ``x (E, ..., c_in) -> (E, ..., c_out)`` in
    ONE kernel launch — the packed replacement for
    ``einsum("ecd,efd->ecf", x, dense_expert_stack)``.

    ``fused_packed (E, tile_n, W)`` / ``fused_scales (E, T * tile_n)``
    are the per-expert buffers of the shared static tile schedule
    (``models/serving.init_deployed_linear(expert_axis=E)``); the grid adds
    a leading E axis (kernels/quant_matmul.quant_matmul_fused_3d).  The
    kernel dequantizes the weight tiles in VMEM **before** the MXU dot, so
    at f32 compute the output matches the dense einsum reference over
    ``dequantize()`` (bit for bit on the CPU interpreter when one grid step
    spans the whole output; see the kernel) — HBM weight traffic stays the
    packed sub-byte bytes.  ``fused_perm`` gathers the output channels exactly as in
    :func:`quant_matmul_fused` (None = restore folded into the walk order).
    """
    E = fused_packed.shape[0]
    if x.ndim < 2 or x.shape[0] != E:
        raise ValueError(
            f"expert-stacked fused matmul needs x of shape (E={E}, ..., "
            f"c_in); got {x.shape}")
    if x.shape[-1] != c_in:
        raise ValueError(
            f"x contraction dim {x.shape[-1]} != c_in {c_in}")
    Kp = -(-c_in // qm_kernel.FUSED_K_ALIGN) * qm_kernel.FUSED_K_ALIGN
    lead = x.shape[1:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(E, M, c_in).astype(compute_dtype)
    x2 = _pad_to(x2, 2, Kp)
    bm_ = _pick_bm(M, bm)
    x2 = _pad_to(x2, 1, bm_)
    y = qm_kernel.quant_matmul_fused_3d(
        x2, fused_packed, fused_scales, tile_bits, Kp=Kp, tile_n=tile_n,
        bm=bm_, out_dtype=out_dtype,
        compute_dtype=compute_dtype)
    y = y[:, :M]
    if fused_perm is not None:
        y = jnp.take(y, fused_perm, axis=-1)
    else:
        y = y[..., :c_out]
    return y.reshape(E, *lead, c_out)


@functools.partial(jax.jit,
                   static_argnames=("tile_bits", "chunk", "tile_n", "c_in",
                                    "c_out", "mesh", "axis", "out_dtype",
                                    "bm", "compute_dtype"))
def quant_matmul_fused_tp(x: jnp.ndarray, fused_packed: jnp.ndarray,
                          fused_scales: jnp.ndarray, fused_perm,
                          tile_bits: tuple, chunk: tuple, tile_n: int,
                          c_in: int, c_out: int, mesh, axis: str = "model",
                          out_dtype=jnp.float32, bm: int = 128,
                          compute_dtype=jnp.float32) -> jnp.ndarray:
    """Tensor-parallel :func:`quant_matmul_fused`: the fused ragged buffer
    (on its byte-column axis) and its scales are sharded along the N-tile
    schedule (``mesh[axis]``
    identical chunks, see ``quant_matmul.tp_chunk``), each device runs the
    SAME single-launch program over its own whole static-bit tiles, and the
    output concatenates along N.  Per-device compute is the unmodified int
    kernel, so the result is bitwise identical to the unsharded launch.
    """
    parts = mesh.shape[axis]
    if chunk * parts != tuple(tile_bits):
        raise ValueError(
            f"chunk {chunk} x {parts} does not tile schedule {tile_bits}")
    if x.shape[-1] != c_in:
        raise ValueError(
            f"x contraction dim {x.shape[-1]} != c_in {c_in}")
    Kp = -(-c_in // qm_kernel.FUSED_K_ALIGN) * qm_kernel.FUSED_K_ALIGN
    lead = x.shape[:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, c_in).astype(compute_dtype)
    x2 = _pad_to(x2, 1, Kp)
    bm_ = _pick_bm(M, bm)
    x2 = _pad_to(x2, 0, bm_)

    def body(xs, fp, fs):
        return qm_kernel.quant_matmul_fused_2d(
            xs, fp, fs, chunk, Kp=Kp, tile_n=tile_n, bm=bm_,
            out_dtype=out_dtype, compute_dtype=compute_dtype)

    y = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(), P(None, axis), P(axis)),
                      out_specs=P(None, axis), check_vma=False)(
        x2, fused_packed, fused_scales)
    y = y[:M]
    if fused_perm is not None:
        y = jnp.take(y, fused_perm, axis=-1)
    else:
        y = y[:, :c_out]
    return y.reshape(*lead, c_out)


@functools.partial(jax.jit,
                   static_argnames=("tile_bits", "tile_n", "c_in", "c_out",
                                    "mesh", "axis", "out_dtype", "bm",
                                    "compute_dtype"))
def quant_matmul_fused_batched_ep(x: jnp.ndarray, fused_packed: jnp.ndarray,
                                  fused_scales: jnp.ndarray, fused_perm,
                                  tile_bits: tuple, tile_n: int, c_in: int,
                                  c_out: int, mesh, axis: str = "model",
                                  out_dtype=jnp.float32, bm: int = 128,
                                  compute_dtype=jnp.float32) -> jnp.ndarray:
    """Expert-parallel :func:`quant_matmul_fused_batched`: the 3-D kernel's
    leading E axis is sharded over ``mesh[axis]`` (every expert keeps its
    full tile schedule), each device launches the batched kernel over its
    own E/parts experts — bitwise identical to the unsharded launch.
    """
    E = fused_packed.shape[0]
    parts = mesh.shape[axis]
    if E % parts:
        raise ValueError(f"E={E} not divisible by mesh[{axis}]={parts}")
    if x.ndim < 2 or x.shape[0] != E:
        raise ValueError(
            f"expert-stacked fused matmul needs x of shape (E={E}, ..., "
            f"c_in); got {x.shape}")
    if x.shape[-1] != c_in:
        raise ValueError(
            f"x contraction dim {x.shape[-1]} != c_in {c_in}")
    Kp = -(-c_in // qm_kernel.FUSED_K_ALIGN) * qm_kernel.FUSED_K_ALIGN
    lead = x.shape[1:-1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(E, M, c_in).astype(compute_dtype)
    x2 = _pad_to(x2, 2, Kp)
    bm_ = _pick_bm(M, bm)
    x2 = _pad_to(x2, 1, bm_)

    def body(xs, fp, fs):
        return qm_kernel.quant_matmul_fused_3d(
            xs, fp, fs, tile_bits, Kp=Kp, tile_n=tile_n, bm=bm_,
            out_dtype=out_dtype, compute_dtype=compute_dtype)

    y = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(axis), P(axis), P(axis)),
                      out_specs=P(axis), check_vma=False)(
        x2, fused_packed, fused_scales)
    y = y[:, :M]
    if fused_perm is not None:
        y = jnp.take(y, fused_perm, axis=-1)
    else:
        y = y[..., :c_out]
    return y.reshape(E, *lead, c_out)


def qtensor_matmul(x: jnp.ndarray, qt, out_dtype=jnp.float32) -> jnp.ndarray:
    """``x (..., c_in) @ QTensor -> (..., c_out)`` on the Pallas path.

    Typed entry point for :class:`repro.api.qtensor.QTensor`.  Routing
    (fused single launch vs per-group), concat and order-restore live in
    ``QTensor.matmul`` (single source of truth for all backends); this
    wrapper just pins the Pallas backend.  ``out_dtype`` defaults to f32,
    matching :func:`qtensor_conv2d` (the bit-parity compute path).
    """
    return qt.matmul(x, out_dtype, backend="pallas")


@functools.partial(jax.jit,
                   static_argnames=("bits", "c_in", "kernel_hw", "stride",
                                    "padding", "out_dtype", "compute_dtype"))
def quant_conv2d(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                 bits: int, c_in: int, kernel_hw: tuple, stride=1,
                 padding: str = "SAME", out_dtype=jnp.float32,
                 compute_dtype=jnp.float32) -> jnp.ndarray:
    """Packed conv of ONE precision group: im2col + fused patch-GEMM.

    ``x (N, H, W, C)`` NHWC against ``packed (n, ceil(c_in/f))`` where
    ``c_in = C * kh * kw`` is the flattened contraction axis (channel-major,
    matching ``(c_out, C, kh, kw).reshape(c_out, -1)``) -> ``(N, Ho, Wo, n)``.
    The dense float kernel is never materialized: packed bytes stream into
    the quant_matmul kernel and unpack in VMEM.  Group concat / channel-order
    restore for a multi-precision ``QTensor`` live in ``QTensor.conv2d``.
    """
    kh, kw = kernel_hw
    patches = qc_kernel.im2col(x, kh, kw, stride, padding)
    return quant_matmul(patches, packed, scale, bits, c_in,
                        out_dtype=out_dtype, compute_dtype=compute_dtype)


def qtensor_conv2d(x: jnp.ndarray, qt, stride=1, padding: str = "SAME",
                   groups: int = 1, out_dtype=jnp.float32) -> jnp.ndarray:
    """NHWC ``x`` * conv :class:`QTensor` -> ``(N, Ho, Wo, c_out)``, Pallas.

    Mirror of :func:`qtensor_matmul` for convolutions: the im2col, group
    loop, concat and order-restore live in ``QTensor.conv2d`` (single source
    of truth for both backends); this wrapper just pins the Pallas backend.
    """
    return qt.conv2d(x, stride=stride, padding=padding, groups=groups,
                     compute_dtype=out_dtype, backend="pallas")


def count_pallas_launches(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call``s one execution of ``fn(*args)`` issues.

    Counts ``pallas_call`` primitives in the traced jaxpr, recursing into
    nested call/scan/cond/``pjit``/``shard_map`` sub-jaxprs — robust against
    jit caching (a cached inner trace never re-enters the ``pl.pallas_call``
    Python wrapper, so monkeypatch counters undercount; the jaxpr is ground
    truth).  Sub-jaxprs are found by walking every eqn param value through
    arbitrary tuple/list/dict nesting, so higher-order primitives that stash
    their body under new param layouts keep counting.  Counts are launches
    per *program*, not per device: a kernel inside ``shard_map`` runs one
    program on every mesh device but counts once, matching the CI guards'
    "how many kernels does one step issue" meaning.  Used by the
    launch-count guard tests and the benchmark's launch column.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)

    def subjaxprs(v):
        stack = [v]
        while stack:
            u = stack.pop()
            if isinstance(u, (tuple, list)):
                stack.extend(u)
            elif isinstance(u, dict):
                stack.extend(u.values())
            elif isinstance(u, jax.extend.core.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jax.extend.core.Jaxpr):
                yield u

    def walk(jpr) -> int:
        n = 0
        for eqn in jpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                for sub in subjaxprs(v):
                    n += walk(sub)
        return n

    return walk(jaxpr.jaxpr)


@functools.partial(jax.jit, static_argnames=("bitwidths",))
def fused_mix(w: jnp.ndarray, gamma_hat: jnp.ndarray, alpha: jnp.ndarray,
              bitwidths=(2, 4, 8)) -> jnp.ndarray:
    """Fused Eq. 5 weight mixture; arbitrary (N, K) via padding."""
    N, K = w.shape
    bn = 256 if N % 256 == 0 else (N if N <= 256 else 1 << 30)
    bk = 512 if K % 512 == 0 else (K if K <= 512 else 1 << 30)
    if bn == 1 << 30 or bk == 1 << 30:
        wp = _pad_to(_pad_to(w, 0, 256), 1, 512)
        gp = _pad_to(gamma_hat, 0, 256)
        ap = jnp.maximum(_pad_to(alpha, 0, 256), 1e-6)
        out = fq_kernel.fused_mix_2d(wp, gp, ap, bitwidths)
        return out[:N, :K]
    return fq_kernel.fused_mix_2d(w, gamma_hat, alpha, bitwidths, bn=bn,
                                  bk=bk)
