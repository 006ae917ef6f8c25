"""`QTensor` — one typed, jit/vmap-capable mixed-precision tensor.

The Sec. III-C deploy transform of a searched linear map produces, per
weight, up to |P_W| fixed-precision channel groups (channels reordered so
each group is contiguous), packed sub-byte into uint8.  ``QTensor`` carries
exactly that:

* ``packed``   — tuple of ``(rows_b, ceil(c_in * b / 8))`` uint8 arrays, one
  per non-empty precision group, ascending bit-width;
* ``scales``   — tuple of ``(rows_b,)`` float32 per-channel dequant steps;
* ``inv_perm`` — ``(c_out,)`` int32 restoring the canonical output channel
  order; the static ``restore_order`` flag says whether ``matmul`` applies
  it (when False the consumer instead permutes the next layer's ``c_in`` —
  the paper's Fig. 2 transform, see
  :func:`repro.core.deploy.propagate_perm`);
* static aux: the ``bits`` tuple, logical ``(c_out, c_in)``, the layer-wise
  activation quantization (``act_bits``/``act_scale``) and, for convolution
  weights, the original kernel tail shape.

With ``tile_n`` set (tile-aligned deploy, the default of ``Engine.deploy``),
the QTensor additionally carries the **fused single-launch layout**: every
precision group's channel count is padded up to the ``tile_n`` output tile
(zero rows, zero scales), the per-tile packed blocks concatenate
column-wise into one ragged ``(tile_n, W)`` byte buffer (``fused_packed``)
with a static per-tile bit-width schedule (``tile_bits``), and
``matmul``/``conv2d`` run the whole multi-precision weight as ONE
``pallas_call`` (kernels/quant_matmul.quant_matmul_fused_2d) — no
per-group launches, no concat.  The schedule's tile walk order is chosen
so that, whenever the canonical-order restore is tile-granular (single
precision group, or already-sorted assignments), the restore folds into
the kernel's output layout and ``fused_perm`` is ``None``; otherwise
``fused_perm`` is a single output gather.

Because it is a **registered pytree** (arrays are leaves, geometry is aux
data), a whole deployed model is just a params tree with ``QTensor`` leaves:
it flows through ``jax.jit`` / ``jax.vmap`` / ``device_put`` unchanged, and
``matmul`` routes through the fused single-launch kernel
(``backend="pallas"``), the per-group reference kernels
(``backend="pallas-pergroup"``) or the jnp fallback.
``conv2d`` lowers an NHWC conv to im2col patches (kernels/quant_conv.py)
and delegates to ``matmul`` — the deployed conv path never materializes a
dense float kernel (depthwise convs take a grouped per-channel fall-back).

With ``experts`` set (static E; the MoE layout built by
``models/serving.init_deployed_linear(expert_axis=E)``), every array leaf
carries a leading expert axis — including the fused buffers, which then
share ONE static tile schedule across experts — and ``matmul`` maps
``(E, ..., c_in) -> (E, ..., c_out)`` as a batched grouped GEMM: the packed
replacement for ``einsum("ecd,efd->ecf", x, dense_stack)``, served as a
single expert-batched ``pallas_call`` under ``backend="pallas"``.

This replaces the old offline-only ``core.deploy.DeployedLinear`` numpy
holder; the search-time, fine-tune, and serving paths now share one type.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantizers as qz
from repro.dist import sharding as shd
from repro.kernels import quant_matmul as qmk

BACKENDS = ("jnp", "pallas", "pallas-pergroup")


def _auto_tile_n(c_out: int) -> int:
    """Default output-tile width: the largest power of two <= c_out, capped
    at the 128-wide MXU lane dimension.  Small edge layers get small tiles
    (bounding the zero-row padding), large layers get full MXU tiles."""
    return min(128, 1 << (max(int(c_out), 1).bit_length() - 1))


def _fused_tile_layout(groups, tile_n: int, Kp: int, c_out: int,
                       restore_order: bool):
    """Build the single-launch fused layout from per-group integer weights.

    ``groups`` is a list of ``(bits, q (n_g, Kp) int8, step (n_g,),
    canon_idx (n_g,))`` in ascending bit-width.  Each group is padded to a
    ``tile_n`` multiple (zero rows / zero scales / target -1) and split into
    tiles; tiles are then ordered by the target position of their first
    (always real) row — canonical position when ``restore_order``, deployed
    position otherwise.  When that walk order lays every real channel at
    its target column with padding only past ``c_out``, the order restore
    has folded into the kernel's identity output index map and the returned
    ``fused_perm`` is None; otherwise it is the (c_out,) output gather.

    Returns ``(fused_packed (tile_n, W) uint8, fused_scales (T*tile_n,)
    f32, fused_perm, tile_bits)``: tile ``t``'s packed bytes are the
    column block ``quant_matmul.fused_tile_offsets(tile_bits, Kp)[t]``
    onward.

    ``models/serving.init_deployed_linear`` carries a traced-safe sibling
    of this builder (jnp ops, schedule from static group sizes only, an
    optional expert axis) for the vmap'd serving init; both pack through
    ``quant_matmul.pack_fused_tiles``.
    """
    tiles = []
    dep_start = 0
    for b, q, step, idx in groups:
        n = q.shape[0]
        assert q.shape[1] == Kp, (q.shape, Kp)
        pad = (-n) % tile_n
        qp = np.pad(np.asarray(q, np.int8), ((0, pad), (0, 0)))
        sp = np.pad(np.asarray(step, np.float32).reshape(-1), (0, pad))
        tgt = (np.asarray(idx, np.int64) if restore_order
               else np.arange(dep_start, dep_start + n, dtype=np.int64))
        tgt = np.concatenate([tgt, np.full(pad, -1, np.int64)])
        dep_start += n
        for t0 in range(0, n + pad, tile_n):
            sl = slice(t0, t0 + tile_n)
            tiles.append((b, qp[sl], sp[sl], tgt[sl]))
    tiles.sort(key=lambda t: int(t[3][0]))
    tile_bits = tuple(t[0] for t in tiles)
    fused_packed = np.concatenate(
        [np.asarray(qmk.pack_fused_tiles(jnp.asarray(q), b, tile_n))
         for b, q, _, _ in tiles], axis=1)
    fused_scales = np.concatenate([t[2] for t in tiles])
    tcol = np.concatenate([t[3] for t in tiles])
    if (tcol[:c_out] == np.arange(c_out)).all() and (tcol[c_out:] < 0).all():
        fused_perm = None                   # restore folded into the walk
    else:
        cols = np.nonzero(tcol >= 0)[0].astype(np.int32)
        fp = np.zeros(c_out, np.int32)
        fp[tcol[cols]] = cols
        fused_perm = jnp.asarray(fp)
    return (jnp.asarray(fused_packed), jnp.asarray(fused_scales),
            fused_perm, tile_bits)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class QTensor:
    packed: tuple                 # tuple[jnp.ndarray] uint8, per group
    scales: tuple                 # tuple[jnp.ndarray] f32,  per group
    inv_perm: Optional[jnp.ndarray]   # (c_out,) i32; None = identity
    bits: tuple                   # static: ascending bit-widths, len==len(packed)
    c_out: int
    c_in: int                     # logical contraction dim (pre-padding)
    act_bits: int = 8
    act_scale: float = 1.0
    kernel_shape: Optional[tuple] = None   # conv tail (c_in/g, kh, kw)
    restore_order: bool = True    # matmul outputs canonical channel order
    # -- fused single-launch layout (tile-aligned deploy; None = absent) ----
    fused_packed: Optional[jnp.ndarray] = None   # (tile_n, W) uint8 ragged
    fused_scales: Optional[jnp.ndarray] = None   # (T * tile_n,) f32
    fused_perm: Optional[jnp.ndarray] = None     # (c_out,) i32 output gather;
    #                                              None = restore folded into
    #                                              the tile walk order
    tile_bits: Optional[tuple] = None            # static per-tile bit-widths
    tile_n: Optional[int] = None                 # static output tile width
    # -- expert stacking (MoE) ---------------------------------------------
    experts: Optional[int] = None   # static E: every array leaf carries a
    #                                 leading expert axis and matmul maps
    #                                 (E, ..., c_in) -> (E, ..., c_out) as a
    #                                 batched grouped GEMM (one launch)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        children = (
            (jax.tree_util.GetAttrKey("packed"), self.packed),
            (jax.tree_util.GetAttrKey("scales"), self.scales),
            (jax.tree_util.GetAttrKey("inv_perm"), self.inv_perm),
            (jax.tree_util.GetAttrKey("fused_packed"), self.fused_packed),
            (jax.tree_util.GetAttrKey("fused_scales"), self.fused_scales),
            (jax.tree_util.GetAttrKey("fused_perm"), self.fused_perm),
        )
        aux = (self.bits, self.c_out, self.c_in, self.act_bits,
               self.act_scale, self.kernel_shape, self.restore_order,
               self.tile_bits, self.tile_n, self.experts)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scales, inv_perm, fused_packed, fused_scales, fperm = children
        (bits, c_out, c_in, act_bits, act_scale, kernel_shape,
         restore_order, tile_bits, tile_n, experts) = aux
        return cls(packed, scales, inv_perm, bits, c_out, c_in,
                   act_bits, act_scale, kernel_shape, restore_order,
                   fused_packed, fused_scales, fperm, tile_bits, tile_n,
                   experts)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_assignment(cls, w, bits_per_channel, alpha_w,
                        bitwidths=(2, 4, 8), align: int = 1,
                        restore_order: bool = True,
                        act_bits: int = 8, act_scale: float = 1.0,
                        tile_n=None) -> "QTensor":
        """Pack a float weight under an explicit per-channel assignment.

        ``w`` is ``(c_out, ...)``; trailing dims flatten into the contraction
        axis (conv kernels keep their tail shape for ``dense()``).

        ``tile_n`` enables the tile-aligned fused single-launch layout: an
        int pins the output tile width, ``"auto"`` picks the largest power
        of two ``<= c_out`` (capped at 128), ``None`` (default) packs only
        the per-group buffers.  With a fused layout the per-group buffers
        are packed at the common ``Kp`` byte width (c_in rounded up to the
        int2 pack factor) so the per-group reference path reduces the exact
        same K columns as the fused kernel — the bit-exactness contract.
        Contractions beyond ``K_SINGLE_STEP_MAX`` stay per-group (the fused
        kernel runs K as a single step).
        """
        from repro.core import deploy as dpl   # local: avoid import cycle
        w = np.asarray(w, np.float32)
        kernel_shape = tuple(w.shape[1:]) if w.ndim > 2 else None
        w2 = w.reshape(w.shape[0], -1)
        c_out, c_in = w2.shape
        bits_per_channel = np.asarray(bits_per_channel)
        alpha = np.asarray(alpha_w, np.float32)
        if alpha.ndim == 0:
            alpha = np.broadcast_to(alpha, (c_out,)).copy()
        perm, sizes = dpl.group_channels(bits_per_channel, bitwidths,
                                         align=align)
        if tile_n == "auto":
            tile_n = _auto_tile_n(c_out)
        Kp = -(-c_in // qmk.FUSED_K_ALIGN) * qmk.FUSED_K_ALIGN
        if tile_n is not None and Kp > qmk.K_SINGLE_STEP_MAX:
            tile_n = None                  # contraction too deep to fuse
        packed, scales, used_bits, groups = [], [], [], []
        offset = 0
        for b in sorted(bitwidths):
            n = sizes[b]
            if n == 0:
                continue
            idx = perm[offset: offset + n]
            offset += n
            q, step = qz.quantize_weight_int(
                jnp.asarray(w2[idx]), jnp.asarray(alpha[idx][:, None]), b)
            q = np.asarray(q)
            f = qz.pack_factor(b)
            kpad = Kp if tile_n is not None else -(-c_in // f) * f
            q = np.pad(q, ((0, 0), (0, kpad - c_in)))
            packed.append(jnp.asarray(qz.pack_int(jnp.asarray(q), b)))
            scales.append(jnp.asarray(step).reshape(-1).astype(jnp.float32))
            used_bits.append(b)
            groups.append((b, q, np.asarray(step).reshape(-1), idx))
        inv_perm = jnp.asarray(np.argsort(perm), jnp.int32)
        fused = dict(fused_packed=None, fused_scales=None, fused_perm=None,
                     tile_bits=None, tile_n=None)
        if tile_n is not None:
            fp, fs, fperm, tile_bits = _fused_tile_layout(
                groups, tile_n, Kp, c_out, restore_order)
            fused = dict(fused_packed=fp, fused_scales=fs, fused_perm=fperm,
                         tile_bits=tile_bits, tile_n=tile_n)
        return cls(tuple(packed), tuple(scales), inv_perm,
                   tuple(used_bits), c_out, c_in,
                   act_bits=act_bits, act_scale=act_scale,
                   kernel_shape=kernel_shape, restore_order=restore_order,
                   **fused)

    # -- geometry -----------------------------------------------------------
    @property
    def group_sizes(self) -> dict:
        return {b: p.shape[-2] for b, p in zip(self.bits, self.packed)}

    @property
    def perm(self) -> np.ndarray:
        """Deployed channel order (original index per deployed row)."""
        if self.inv_perm is None:
            return np.arange(self.c_out)
        return np.argsort(np.asarray(self.inv_perm))

    @property
    def memory_bits(self) -> int:
        """Deployed model-size contribution in bits (the Pareto x-axis).

        With a fused layout this is the ragged single-launch buffer — the
        weight bytes a deployed edge artifact ships, tile padding (zero
        rows up to ``tile_n``, K rounded to the int2 pack factor) included.
        Without one it is the per-group packed bytes, as before.  Note this
        models the *deployment* footprint: in-repo a tile-aligned QTensor
        additionally keeps the per-group buffers as live leaves (they back
        the ``pallas-pergroup``/``jnp`` reference paths, the depthwise
        fall-back and ``dequantize``), so host/device memory of this
        development representation is roughly double the reported figure.
        """
        if self.fused_packed is not None:
            return int(self.fused_packed.size) * 8
        return sum(int(p.size) * 8 for p in self.packed)

    # -- compute ------------------------------------------------------------
    def _group_dense(self, b: int, p: jnp.ndarray, s: jnp.ndarray,
                     compute_dtype) -> jnp.ndarray:
        """Unpack + dequant ONE precision group to ``(rows_b, c_in)`` — the
        jnp fall-back's small per-group materialization (never the whole
        canonical weight)."""
        w_int = qz.unpack_int(p, b)[..., : self.c_in]
        return (w_int.astype(jnp.float32) * s[..., None]).astype(compute_dtype)

    def _concat_restore(self, outs: list) -> jnp.ndarray:
        """Concat per-precision group outputs (deployed channel order) and
        restore canonical order — the single tail shared by ``matmul`` and
        both ``conv2d`` paths so the backends/layouts cannot drift."""
        y = jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]
        if self.restore_order and self.inv_perm is not None:
            y = jnp.take(y, self.inv_perm, axis=-1)
        return y

    def _dequantize_groups(self) -> jnp.ndarray:
        """Float weight stack in **deployed** (group-contiguous) order."""
        outs = [self._group_dense(b, p, s, jnp.float32)
                for b, p, s in zip(self.bits, self.packed, self.scales)]
        return jnp.concatenate(outs, axis=-2) if len(outs) > 1 else outs[0]

    def dequantize_canonical(self, dtype=jnp.float32) -> jnp.ndarray:
        """Float ``(c_out, c_in)`` in canonical channel order regardless of
        ``restore_order`` — the analysis/reference view (tests, Pareto)."""
        w = self._dequantize_groups()
        if self.inv_perm is not None:
            w = jnp.take(w, self.inv_perm, axis=-2)
        return w.astype(dtype)

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        """Float ``(c_out, c_in)`` view in the same channel order ``matmul``
        produces: canonical when ``restore_order`` (the default), deployed
        (group-contiguous) otherwise — so dense-view consumers always agree
        with the packed runtime path."""
        w = self._dequantize_groups()
        if self.restore_order and self.inv_perm is not None:
            w = jnp.take(w, self.inv_perm, axis=-2)
        return w.astype(dtype)

    def dense(self, dtype=jnp.float32) -> jnp.ndarray:
        """``dequantize`` with the conv kernel tail restored."""
        w = self.dequantize(dtype)
        if self.kernel_shape is not None:
            w = w.reshape((self.c_out,) + self.kernel_shape)
        return w

    def matmul(self, x: jnp.ndarray, compute_dtype=jnp.float32,
               backend: str = "jnp") -> jnp.ndarray:
        """``x (..., c_in) -> (..., c_out)`` on one of three backends:

        * ``"pallas"`` — the serving hot path: with a fused layout (tile-
          aligned deploy) the whole multi-precision weight runs as ONE
          ``pallas_call`` (kernels/quant_matmul.quant_matmul_fused_2d), the
          order restore folded into the tile schedule (or a single output
          gather); without one it falls back to the per-group kernels.
        * ``"pallas-pergroup"`` — the per-group reference path: one
          unpack+dequant+GEMM kernel launch per precision group, outputs
          concatenated (the paper's parallel sub-convolutions), then the
          canonical-order restore when ``restore_order``.
        * ``"jnp"`` — per-group dense fallback (no Pallas).

        This method owns the routing and the concat/restore so the
        backends cannot drift.  ``compute_dtype`` reaches the kernel's MXU
        dot as well as the output cast: f32 (the default) is the bit-parity
        path with the fake-quant reference, bf16 the TPU fast path.

        An **expert-stacked** QTensor (``experts == E``; MoE weight stacks
        from ``serving.init_deployed_linear(expert_axis=E)``) instead maps
        ``x (E, ..., c_in) -> (E, ..., c_out)`` per expert — the packed
        form of ``einsum("ecd,efd->ecf", x, dense_stack)``; with a fused
        layout the whole grouped GEMM is ONE expert-batched launch.
        """
        if x.shape[-1] != self.c_in:
            raise ValueError(
                f"x contraction dim {x.shape[-1]} != c_in {self.c_in} "
                "(all backends reject this — the Pallas kernel would "
                "otherwise zero-pad and compute silently wrong outputs)")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        if self.experts is not None:
            return self._matmul_experts(x, compute_dtype, backend)
        if backend == "pallas" and self.fused_packed is not None:
            from repro.kernels import ops as kops
            ctx = shd.serving_ctx()
            if ctx is not None and ctx.model > 1:
                chunk = qmk.tp_chunk(self.tile_bits, ctx.model)
                if chunk is not None:
                    return kops.quant_matmul_fused_tp(
                        x, self.fused_packed, self.fused_scales,
                        self.fused_perm, self.tile_bits, chunk, self.tile_n,
                        self.c_in, self.c_out, ctx.mesh,
                        out_dtype=compute_dtype, compute_dtype=compute_dtype)
            return shd.replicated_kernel(
                lambda x, fp, fs, perm: kops.quant_matmul_fused(
                    x, fp, fs, perm, self.tile_bits, self.tile_n, self.c_in,
                    self.c_out, out_dtype=compute_dtype,
                    compute_dtype=compute_dtype))(
                x, self.fused_packed, self.fused_scales, self.fused_perm)
        if backend in ("pallas", "pallas-pergroup"):
            from repro.kernels import ops as kops
            c_in, bn = self.c_in, 128
            if self.tile_n is not None:
                # fused-layout per-group buffers are packed at the common
                # Kp byte width: feed the kernel the same padded columns,
                # and contract the fused kernel's (tile_n, Kp) dot shape
                Kp = self.packed[-1].shape[-1] * qz.pack_factor(self.bits[-1])
                widths = [(0, 0)] * (x.ndim - 1) + [(0, Kp - c_in)]
                x = jnp.pad(x, widths)
                c_in, bn = Kp, self.tile_n

            def gemm(b, p, s):
                return shd.replicated_kernel(
                    lambda x, p, s: kops.quant_matmul(
                        x, p, s, b, c_in, bn=bn, out_dtype=compute_dtype,
                        compute_dtype=compute_dtype))(x, p, s)
        else:
            def gemm(b, p, s):
                w = self._group_dense(b, p, s, compute_dtype)
                return jnp.einsum("...i,oi->...o", x.astype(compute_dtype), w)
        outs = [gemm(b, p, s)
                for b, p, s in zip(self.bits, self.packed, self.scales)]
        return self._concat_restore(outs)

    def _matmul_experts(self, x: jnp.ndarray, compute_dtype,
                        backend: str) -> jnp.ndarray:
        """Stacked-leaf (MoE) dispatch: ``x (E, ..., c_in) -> (E, ...,
        c_out)``, each expert contracting its own packed weight.

        ``backend="pallas"`` with a fused layout runs the whole grouped
        GEMM as ONE expert-batched launch
        (kernels/ops.quant_matmul_fused_batched — bit-exact at f32 with
        the dense einsum reference); otherwise the per-group kernels run
        per expert (the reference path), or the jnp fall-back contracts
        each group's small dense slice with a batched einsum.  The serving
        hot path never dequantizes the full ``(E, c_out, c_in)`` stack.
        """
        E = self.experts
        if x.ndim < 2 or x.shape[0] != E:
            raise ValueError(
                f"expert-stacked QTensor (experts={E}) takes x of shape "
                f"(E, ..., c_in); got {x.shape}")
        if backend == "pallas" and self.fused_packed is not None:
            from repro.kernels import ops as kops
            ctx = shd.serving_ctx()
            if ctx is not None and ctx.model > 1 and E % ctx.model == 0:
                return kops.quant_matmul_fused_batched_ep(
                    x, self.fused_packed, self.fused_scales, self.fused_perm,
                    self.tile_bits, self.tile_n, self.c_in, self.c_out,
                    ctx.mesh, out_dtype=compute_dtype,
                    compute_dtype=compute_dtype)
            return shd.replicated_kernel(
                lambda x, fp, fs, perm: kops.quant_matmul_fused_batched(
                    x, fp, fs, perm, self.tile_bits, self.tile_n, self.c_in,
                    self.c_out, out_dtype=compute_dtype,
                    compute_dtype=compute_dtype))(
                x, self.fused_packed, self.fused_scales, self.fused_perm)
        if backend in ("pallas", "pallas-pergroup"):
            from repro.kernels import ops as kops
            c_in, bn = self.c_in, 128
            if self.tile_n is not None:
                Kp = self.packed[-1].shape[-1] * qz.pack_factor(self.bits[-1])
                widths = [(0, 0)] * (x.ndim - 1) + [(0, Kp - c_in)]
                x = jnp.pad(x, widths)
                c_in, bn = Kp, self.tile_n

            def gemm(b, p, s):
                one = shd.replicated_kernel(
                    lambda x, p, s: kops.quant_matmul(
                        x, p, s, b, c_in, bn=bn, out_dtype=compute_dtype,
                        compute_dtype=compute_dtype))
                return jnp.stack([one(x[e], p[e], s[e]) for e in range(E)])
        else:
            def gemm(b, p, s):
                w = self._group_dense(b, p, s, compute_dtype)  # (E, n, c_in)
                return jnp.einsum("e...i,eoi->e...o",
                                  x.astype(compute_dtype), w)
        outs = [gemm(b, p, s)
                for b, p, s in zip(self.bits, self.packed, self.scales)]
        return self._concat_restore(outs)

    def conv2d(self, x: jnp.ndarray, stride=1, padding: str = "SAME",
               groups: int = 1, compute_dtype=jnp.float32,
               backend: str = "jnp") -> jnp.ndarray:
        """NHWC conv ``x (N, H, W, C) -> (N, Ho, Wo, c_out)`` fully packed.

        The deployed realization of the paper's parallel per-precision
        sub-convolutions: the input is lowered to im2col patches once
        (feature axis channel-major — the exact ``(c_out, c_in*kh*kw)``
        contraction layout this QTensor packs), then **delegates to**
        :meth:`matmul`, so the per-group sub-GEMMs, Pallas/jnp backend
        split, concat and canonical-order restore are one code path for
        linear and conv and cannot drift.  No dense float kernel is ever
        materialized.

        Depthwise weights (``groups == c_out``, kernel tail ``(1, kh, kw)``
        — DS-CNN/MobileNetV1 ``dwconv``) contract only the ``kh*kw`` taps of
        their own channel, which is not a single GEMM; they take the grouped
        fall-back below: per-precision-group gather of the channel-major
        patches + a tiny ``(rows, kh*kw)`` group unpack (the same amount the
        jnp matmul fall-back unpacks), identical for both backends.
        """
        if self.kernel_shape is None:
            raise TypeError("conv2d requires a conv QTensor "
                            "(kernel_shape is None — this is a linear map)")
        if self.experts is not None:
            raise TypeError("conv2d does not take expert-stacked QTensors "
                            "(the expert axis is a linear-map concept)")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        from repro.kernels import quant_conv as qc

        kh, kw = self.kernel_shape[-2:]
        if groups == 1:
            patches = qc.im2col(x, kh, kw, stride, padding)
            return self.matmul(patches, compute_dtype, backend)
        if groups != self.c_out or self.kernel_shape[0] != 1 \
                or x.shape[-1] != groups:
            raise NotImplementedError(
                f"grouped conv with groups={groups} (c_out={self.c_out}, "
                f"kernel_shape={self.kernel_shape}): only groups=1 and "
                "depthwise (groups == c_out, tail (1, kh, kw)) are packed")
        # -- depthwise fall-back: per-channel tap contraction ---------------
        patches = qc.depthwise_patches(x, kh, kw, stride, padding)
        if self.inv_perm is not None:
            # gather input channels into deployed (group-contiguous) order;
            # traced-safe (jnp.argsort, not the numpy .perm property)
            patches = jnp.take(patches, jnp.argsort(self.inv_perm), axis=-2)
        outs, offset = [], 0
        for b, p, s in zip(self.bits, self.packed, self.scales):
            rows = p.shape[-2]
            w = self._group_dense(b, p, s, compute_dtype)   # (rows, kh*kw)
            seg = patches[..., offset: offset + rows, :].astype(compute_dtype)
            outs.append(jnp.einsum("...ck,ck->...c", seg, w))
            offset += rows
        return self._concat_restore(outs)


def requantize(qt: QTensor, bits: int) -> QTensor:
    """Re-quantize a deployed QTensor to a uniform ``bits`` assignment.

    The one-checkpoint-many-precisions derivation behind speculative
    drafting (models/serving.draft_model): dequantize the searched deploy
    back to its canonical float view, then re-pack every channel at the
    single aggressive ``bits`` with fresh per-channel amax clipping — a new
    static assignment, NOT a lossy cast of the packed bytes.  Layer-stacked
    (scan) and expert-stacked (MoE) leaves round-trip: leading stack axes
    are rebuilt slice by slice offline and restacked, preserving the shared
    static tile schedule, and ``experts`` is restored on the result.  The
    fused single-launch layout, ``restore_order``, activation quantization
    and conv kernel tail all carry over.
    """
    if bits not in (2, 4, 8):
        raise ValueError(f"requantize bits must be one of (2, 4, 8); "
                         f"got {bits}")
    deq = lambda t: t.dequantize_canonical(jnp.float32)
    for _ in range(qt.packed[0].ndim - 2):      # layer/expert stack axes
        deq = jax.vmap(deq)
    w = np.asarray(deq(qt))                     # (*stack, c_out, c_in)
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    assign = np.full(qt.c_out, bits, np.int64)
    rebuilt = []
    for i in range(flat.shape[0]):
        wi = flat[i]
        alpha = np.maximum(np.max(np.abs(wi), axis=1), 1e-8)
        if qt.kernel_shape is not None:
            wi = wi.reshape((qt.c_out,) + qt.kernel_shape)
        rebuilt.append(QTensor.from_assignment(
            wi, assign, alpha, bitwidths=(2, 4, 8),
            restore_order=qt.restore_order, act_bits=qt.act_bits,
            act_scale=qt.act_scale, tile_n=qt.tile_n))
    if not lead:
        return rebuilt[0]
    out = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls).reshape(lead + ls[0].shape), *rebuilt)
    if qt.experts is not None:
        out = dataclasses.replace(out, experts=qt.experts)
    return out
