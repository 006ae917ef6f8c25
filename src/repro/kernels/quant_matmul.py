"""Pallas TPU kernel: packed sub-byte weight matmul with in-VMEM dequant.

The deployment hot-spot of the paper's technique on TPU: one
per-precision channel group of a deployed linear is

    y[m, n] = scale[n] * sum_k x[m, k] * w_int[n, k]

with ``w_int`` stored *packed* (4x int2 / 2x int4 / 1x int8 per uint8 byte)
in HBM.  The kernel streams packed bytes HBM->VMEM (the point: weight
bandwidth scales with the searched bit-width), unpacks + sign-extends in
VMEM registers (``core.quantizers.unpack_int``: the chunked planar byte
layout, which the TPU compiler lowers without a lane interleave), runs the
MXU dot at bf16/f32, and applies the per-channel scale once at the end of
the K loop.

Tiling: grid (M/bm, N/bn, K/bk); x block (bm, bk), packed block
(bn, bk/pack_factor), scale block (1, bn), output block (bm, bn)
accumulated across the K grid axis (output revisiting — the standard
Pallas matmul reduction pattern).
Block defaults bm=bn=128, bk=512 keep the working set
(128*512*2 + 128*512 + 128*128*4)B ≈ 0.4 MB well under the ~16 MB VMEM
budget while keeping the MXU dimensions 128-aligned.

Validated in interpret mode on CPU against ref.quant_matmul_ref across a
shape/dtype/bits sweep (tests/test_kernels.py) and compiled for v5e at real
widths (tests/test_tpu_compile.py); ``repro.kernels.interpret_mode``
compiles every kernel on a TPU.

Fused multi-precision launch (``quant_matmul_fused_2d``)
--------------------------------------------------------
The deployed realization of the paper's parallel per-precision
sub-convolutions used to be literal: one ``pallas_call`` per precision
group, a concat, and an order-restore gather on every forward.  For the
edge-class GEMMs this repo serves, that dispatch-and-stitch tax dominates.
The fused kernel runs **all** precision groups of a deployed weight in a
single launch:

* deploy-time packing is *tile-aligned* — every precision group's channel
  count is padded up to the ``tile_n`` output tile, so each ``tile_n``-wide
  output tile has exactly one static bit-width;
* the per-group packed buffers concatenate column-wise into one ragged
  ``(tile_n, W)`` byte buffer (tile ``t`` owns ``Kp*b/8`` byte columns,
  tight — low-bit tiles really occupy fewer bytes);
* the grid is ``(M/bm, T/tpb)``: a step computes ``tpb`` output tiles
  (one 128-multiple tile, or all tiles of a narrow layer;
  ``fused_tiles_per_step``).  The per-tile bit-width and column offset
  come from a **static schedule** (``tile_bits``), unrolled at trace time,
  so each tile reads exactly its own bytes and unpacks at its own width —
  no per-group launches, no concat in HBM.  The packed buffer is one
  resident block, so the scoped VMEM limit is raised to hold it double
  buffered (``_fused_vmem_limit``: deepseek-v3's expert down projection,
  K=2048 x N=7168, needs about 21 MB);
* the tile walk order is chosen at deploy time (api/qtensor.py): when the
  canonical-order restore is tile-granular the schedule itself visits tiles
  in canonical output order and the restore folds into the output block
  layout — the old ``_concat_restore`` gather disappears from the hot path
  entirely.

K is not gridded: edge GEMMs have small contractions, so each tile does one
MXU dot over the whole (padded) ``Kp <= K_SINGLE_STEP_MAX``.  This is also
what makes the fused path bit-exact with the per-group path at
``compute_dtype=f32`` — both reduce K in a single dot of identical shape
(the per-group reference runs at ``bn = tile_n``).

Batched / expert axis (``quant_matmul_fused_3d``)
-------------------------------------------------
MoE expert stacks carry a leading ``E`` axis on every buffer (the
``init_deployed_linear(expert_axis=E)`` layout: one static tile schedule
shared by all experts, per-expert packed bytes and scales).  The fused
kernel extends with one more grid dimension — ``grid (E, M/bm, T/tpb)``
— so a whole ``einsum("ecd,efd->ecf")``-shaped grouped expert GEMM is
still ONE ``pallas_call``; the per-tile static bit schedule is unchanged,
each grid step just reads expert ``e``'s ragged buffer.  The 2-D entry point
is the ``E == 1`` slice of the same kernel body.

Two in-kernel scale placements (static ``dequant_first`` flag):

* ``False`` (the 2-D / single-weight path): the per-channel step multiplies
  the f32 *accumulator* after the dot — bit-exact with the per-group
  ``_kernel`` (PR 3's contract).
* ``True`` (the expert-batched path): the step multiplies the unpacked
  integer tiles *before* one dot over its tiles (in-VMEM dequant; HBM
  traffic is still the packed bytes).  The products then match a dense
  reference ``einsum("ecd,efd->ecf", x, w_int * scale)`` element for
  element, and where a step spans the whole output (every narrow expert
  site) the fused expert GEMM is **bit-exact at f32 with the dense einsum
  it replaces** on the CPU interpreter (`models/serving._deployed_moe`'s
  old ``dq_expert_weights`` path).  XLA's CPU GEMM rounds by output width,
  so a step of one 128-wide tile agrees with a wider einsum to f32
  roundoff only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantizers as qz
from repro.kernels import interpret_mode

# Contractions up to this many (padded) columns run as ONE K step — a single
# MXU dot — in both the per-group and the fused kernel.  Keeping the two
# paths on the same K schedule is what makes them bit-exact at f32 compute
# (f32 addition is not associative; identical reduction shape => identical
# rounding).  Larger K falls back to the chunked-accumulation grid.
K_SINGLE_STEP_MAX = 2048

# Byte granularity every fused buffer pads K to: the largest pack factor
# (int2 -> 4 values/byte), so one common Kp serves all bit-widths.
FUSED_K_ALIGN = 4


def _kernel(x_ref, p_ref, s_ref, o_ref, *, bits: int, k_steps: int,
            out_dtype, compute_dtype):
    k = pl.program_id(2)
    w_int = qz.unpack_int(p_ref[...], bits)                 # (bn, bk) int8
    x = x_ref[...]                                          # (bm, bk)
    acc = jnp.dot(x.astype(compute_dtype), w_int.astype(compute_dtype).T,
                  preferred_element_type=jnp.float32)       # (bm, bn)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += acc

    @pl.when(k == k_steps - 1)
    def _scale():
        o_ref[...] *= s_ref[...].astype(jnp.float32)        # (1, bn)


def pick_bk(Kp: int, f: int, bk: int = 512) -> int:
    """K tile size shared by the per-group and fused paths.

    Single step (``bk = Kp``) whenever the padded contraction fits
    ``K_SINGLE_STEP_MAX`` — the edge-GEMM case and the bit-exactness
    contract with the fused kernel; otherwise the largest power-of-two
    divisor of ``Kp`` not above ``bk`` that spans whole packed chunks
    (``core.quantizers.PACK_CHUNK`` bytes), falling back to one step when
    there is none.
    """
    if Kp <= K_SINGLE_STEP_MAX:
        return Kp
    step = qz.PACK_CHUNK * f        # a K block holds whole packed chunks
    bk_ = bk
    while bk_ >= step:
        if Kp % bk_ == 0 and bk_ % step == 0:
            return bk_
        bk_ //= 2
    return Kp


def fused_tile_bytes(bits: int, Kp: int, tile_n: int) -> int:
    """Byte footprint of ONE output tile in the ragged fused buffer."""
    return tile_n * (Kp // qz.pack_factor(bits))


def fused_tile_offsets(tile_bits, Kp: int) -> tuple:
    """Static per-tile column offsets into the ``(tile_n, W)`` fused buffer
    (walk order): tile ``t`` owns the ``Kp / pack_factor`` byte columns
    starting at ``offsets[t]``."""
    offs, off = [], 0
    for b in tile_bits:
        offs.append(off)
        off += Kp // qz.pack_factor(b)
    return tuple(offs)


# Rank of each fused-layout leaf of one weight; expert and layer axes
# stack in front of it.
FUSED_LEAF_RANK = {"fused_packed": 2, "fused_scales": 1}


def pack_fused_tiles(q: jnp.ndarray, bits: int, tile_n: int) -> jnp.ndarray:
    """Pack a run of whole output tiles into their fused column blocks.

    ``q (..., nt * tile_n, Kp)`` holds integers of ``bits``; returns
    ``(..., tile_n, nt * Kp / pack_factor)`` uint8, tile ``t``'s packed
    rows being the column block ``t * Kp / pack_factor`` onward.  Both
    fused builders (``api/qtensor`` and ``models/serving``) pack through
    this and concatenate the runs, in walk order, along the last axis.
    """
    p = qz.pack_int(q, bits)
    *lead, n, w = p.shape
    p = p.reshape(*lead, n // tile_n, tile_n, w)
    return jnp.swapaxes(p, -3, -2).reshape(*lead, tile_n, n // tile_n * w)


def tp_chunk(tile_bits, parts: int):
    """Per-shard tile schedule for ``parts``-way tensor parallelism.

    shard_map traces ONE program for every shard, so the fused buffer can
    only split across devices when the schedule is periodic with period
    T/parts — each device then owns the same sequence of whole static-bit
    tiles (and therefore the same byte count).  Returns that per-shard
    schedule, or None when the schedule does not divide (caller replicates).
    """
    if not tile_bits or parts <= 1:
        return None
    T = len(tile_bits)
    if T % parts:
        return None
    chunk = tuple(tile_bits[:T // parts])
    if tuple(tile_bits) != chunk * parts:
        return None
    return chunk


def fused_tiles_per_step(tile_n: int, T: int) -> int:
    """Output tiles one fused grid step computes.

    One when ``tile_n`` is a whole number of 128-lane vregs: the step's
    output, scale and unpacked-weight blocks then stay one tile wide at any
    layer width.  All ``T`` otherwise: the TPU compiler takes a narrower
    output block only when it spans the whole output, and such tiles only
    arise on narrow layers.
    """
    return 1 if tile_n % 128 == 0 else T


def _fused_vmem_limit(bm: int, Kp: int, tile_n: int, W: int, tpb: int,
                      x_bytes: int) -> int:
    """Scoped VMEM for one fused launch: the double-buffered blocks (the
    resident packed buffer dominates at wide layers) plus the unpacked
    tiles of one step, never below 32 MiB."""
    n = tpb * tile_n
    blocks = bm * Kp * x_bytes + tile_n * W + 128 * n * 4 + bm * n * 4
    return max(2 * blocks + 3 * n * Kp * 4, 32 << 20)


def _tile_runs(tile_bits, offsets, Kp: int) -> list:
    """Group consecutive tiles of one bit-width into ``(first tile, tiles,
    bits, first column)`` runs, one branch of the one-tile-per-step kernel
    each.  A run is extended only while its tiles' column blocks are
    128-lane aligned, so a tile's columns can be sliced at an offset
    computed from the grid index; an unaligned tile is a run of its own,
    sliced at its static offset."""
    runs = []
    for t, (b, off) in enumerate(zip(tile_bits, offsets)):
        width = Kp // qz.pack_factor(b)
        r = runs[-1] if runs else None
        if (r is not None and r[2] == b and width % 128 == 0
                and r[3] % 128 == 0):
            runs[-1] = (r[0], r[1] + 1, b, r[3])
        else:
            runs.append((t, 1, b, off))
    return runs


def _fused_kernel(x_ref, p_ref, s_ref, o_ref, *, tile_bits, offsets,
                  tile_n: int, Kp: int, tpb: int, compute_dtype,
                  dequant_first: bool):
    """One grid step = the ``(bm, tpb * tile_n)`` output column block
    ``pl.program_id(2)`` of one batch slice, every tile at its static
    bit-width.

    With all tiles in one step (``tpb == T``) the ``(bits, column offset)``
    schedule is unrolled at trace time and every slice is static.  With one
    tile per step each run of same-width tiles (``_tile_runs``) is one
    ``pl.when`` branch, its column offset computed from the grid index: a
    handful of branches however many tiles the layer has, which keeps the
    compiler's per-branch temporaries bounded.  ``dequant_first`` picks the
    scale placement (module docstring): accumulator-scaled, one dot per tile
    (per-group bit-parity; ``s_ref`` is a ``(1, tpb * tile_n)`` row) vs
    weight-scaled in VMEM, one dot over the step's tiles (dense-einsum
    bit-parity, the expert path; ``s_ref`` is a ``(tpb * tile_n, 1)``
    column).
    """
    x = x_ref[...].astype(compute_dtype)                    # (bm, Kp)

    def gemm(tiles):
        """``tiles``: ``(bits, column start)`` of the step's tiles."""
        w_int = [qz.unpack_int(p_ref[:, pl.ds(c0, Kp // qz.pack_factor(b))],
                               b) for b, c0 in tiles]       # (tile_n, Kp)
        if dequant_first:
            w = [wt.astype(jnp.float32)
                 * s_ref[i * tile_n:(i + 1) * tile_n, :].astype(jnp.float32)
                 for i, wt in enumerate(w_int)]
            w = (w[0] if len(w) == 1 else jnp.concatenate(w, 0)
                 ).astype(compute_dtype)
            o_ref[...] = jnp.dot(x, w.T, preferred_element_type=jnp.float32)
        else:
            acc = [jnp.dot(x, wt.astype(compute_dtype).T,
                           preferred_element_type=jnp.float32)
                   for wt in w_int]
            acc = acc[0] if len(acc) == 1 else jnp.concatenate(acc, -1)
            o_ref[...] = acc * s_ref[...].astype(jnp.float32)

    if tpb == len(tile_bits):
        gemm(list(zip(tile_bits, offsets)))
        return
    j = pl.program_id(2)
    for t0, n, b, c0 in _tile_runs(tile_bits, offsets, Kp):
        width = Kp // qz.pack_factor(b)
        if n == 1:
            col = c0
        else:
            col = pl.multiple_of(c0 + (j - t0) * width, 128)
        pl.when((j >= t0) & (j < t0 + n))(
            functools.partial(gemm, [(b, col)]))


def quant_matmul_fused_3d(x: jnp.ndarray, fused_packed: jnp.ndarray,
                          fused_scales: jnp.ndarray, tile_bits: tuple, *,
                          Kp: int, tile_n: int, bm: int = 128,
                          interpret: bool | None = None,
                          out_dtype=jnp.float32, compute_dtype=jnp.float32,
                          dequant_first: bool = True) -> jnp.ndarray:
    """Batched (expert-axis) single-launch multi-precision grouped GEMM.

    ``x (E, M, Kp)`` (M a ``bm`` multiple, Kp the common pack-padded
    contraction) x ``fused_packed (E, tile_n, W)`` uint8 ->
    ``(E, M, T * tile_n)`` f32 in tile walk order: the whole
    ``einsum("ecd,efd->ecf")``-shaped expert GEMM in ONE ``pallas_call``,
    grid ``(E, M/bm, T/tpb)`` with ``tpb = fused_tiles_per_step``.  Tile
    ``t`` of the static per-tile bit-width schedule ``tile_bits`` (shared
    by every expert) owns the byte columns
    ``fused_tile_offsets(tile_bits, Kp)[t]`` onward; each expert's whole
    buffer stays resident in VMEM across its grid steps.
    ``fused_scales (E, T * tile_n)`` carries the per-expert per-channel
    dequant steps (0 for tile-padding rows).  ``dequant_first=True`` (the
    expert default) scales the unpacked integer tiles in VMEM before the
    MXU dot.  At f32 on the CPU interpreter this is bit-exact with the
    dense einsum over ``w_int * scale`` when a step spans the whole output
    (``tile_n`` below 128, every expert site of the reduced configs): XLA's
    CPU GEMM rounds by output width, so one-tile steps agree with it to f32
    roundoff only.
    """
    E, M = x.shape[0], x.shape[1]
    T = len(tile_bits)
    assert M % bm == 0 and x.shape[2] == Kp, (x.shape, bm, Kp)
    assert Kp % FUSED_K_ALIGN == 0 and Kp <= K_SINGLE_STEP_MAX, Kp
    offsets = fused_tile_offsets(tile_bits, Kp)
    W = offsets[-1] + Kp // qz.pack_factor(tile_bits[-1])
    assert fused_packed.shape == (E, tile_n, W), \
        (fused_packed.shape, E, tile_n, W,
         "fused buffer does not match schedule")
    assert fused_scales.shape == (E, T * tile_n), fused_scales.shape
    tpb = fused_tiles_per_step(tile_n, T)
    n = tpb * tile_n
    if dequant_first:
        scales = fused_scales.reshape(E, T * tile_n, 1)
        s_spec = pl.BlockSpec((None, n, 1), lambda e, i, j: (e, j, 0))
    else:
        scales = fused_scales.reshape(E, 1, T * tile_n)
        s_spec = pl.BlockSpec((None, 1, n), lambda e, i, j: (e, 0, j))
    kern = functools.partial(_fused_kernel, tile_bits=tuple(tile_bits),
                             offsets=offsets, tile_n=tile_n, Kp=Kp, tpb=tpb,
                             compute_dtype=compute_dtype,
                             dequant_first=dequant_first)
    out = pl.pallas_call(
        kern,
        grid=(E, M // bm, T // tpb),
        in_specs=[
            pl.BlockSpec((None, bm, Kp), lambda e, i, j: (e, i, 0)),
            # an (i, j)-constant index map fetches each expert's buffer once
            pl.BlockSpec((None, tile_n, W), lambda e, i, j: (e, 0, 0)),
            s_spec,
        ],
        # tiles land in walk order: when the deploy transform orders the
        # schedule by canonical output tile, this IS the order restore
        out_specs=pl.BlockSpec((None, bm, n), lambda e, i, j: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M, T * tile_n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_fused_vmem_limit(bm, Kp, tile_n, W, tpb,
                                               x.dtype.itemsize)),
        interpret=interpret_mode(interpret),
        name="quant_matmul_fused",
    )(x, fused_packed, scales)
    return out.astype(out_dtype)


def quant_matmul_fused_2d(x: jnp.ndarray, fused_packed: jnp.ndarray,
                          fused_scales: jnp.ndarray, tile_bits: tuple, *,
                          Kp: int, tile_n: int, bm: int = 128,
                          interpret: bool | None = None,
                          out_dtype=jnp.float32,
                          compute_dtype=jnp.float32) -> jnp.ndarray:
    """Single-launch multi-precision GEMM over a ragged-packed buffer.

    ``x (M, Kp)`` (M a ``bm`` multiple, Kp the common pack-padded
    contraction) x ``fused_packed (tile_n, W)`` uint8 ->
    ``(M, T * tile_n)`` f32 in tile walk order.  ``tile_bits`` is the static
    per-tile bit-width schedule; ``fused_scales (T * tile_n,)`` carries the
    per-channel dequant steps (0 for tile-padding rows).  One ``pallas_call``
    regardless of how many precisions the weight mixes.

    The ``E == 1`` slice of :func:`quant_matmul_fused_3d` with the
    accumulator-scale placement (``dequant_first=False``) — bit-exact at
    f32 with the per-group ``_kernel`` path run at ``bn = tile_n``.
    """
    assert fused_scales.shape == (len(tile_bits) * tile_n,), \
        fused_scales.shape
    out = quant_matmul_fused_3d(
        x[None], fused_packed[None], fused_scales[None], tile_bits, Kp=Kp,
        tile_n=tile_n, bm=bm, interpret=interpret, out_dtype=out_dtype,
        compute_dtype=compute_dtype, dequant_first=False)
    return out[0]


def quant_matmul_2d(x: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
                    bits: int, *, bm: int = 128, bn: int = 128,
                    bk: int = 512, interpret: bool | None = None,
                    out_dtype=jnp.float32,
                    compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """x (M, K) x packed (N, K/f) -> (M, N) f32; M/N/K already padded.

    ``compute_dtype`` is the MXU input dtype: bf16 (default, the TPU fast
    path — int weights <= 127 are bf16-exact so only the activations round)
    or f32 (full-precision parity with the fake-quant reference at the cost
    of MXU passes — what ``QTensor.matmul``/``conv2d`` use by default).
    Accumulation is always f32.
    """
    M, K = x.shape
    N = packed.shape[0]
    f = qz.pack_factor(bits)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % f == 0 and packed.shape[1] == K // f
    k_steps = K // bk
    kern = functools.partial(_kernel, bits=bits, k_steps=k_steps,
                             out_dtype=out_dtype, compute_dtype=compute_dtype)
    out = pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk // f), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret_mode(interpret),
        name="quant_matmul",
    )(x, packed, scale.reshape(1, N))
    return out.astype(out_dtype)
