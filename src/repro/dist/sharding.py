"""Sharding-rules engine: parameter-path -> PartitionSpec mapping.

One declarative rule table maps every leaf of the train / serve state trees
onto the (data, model) mesh (launch/mesh.py):

* **column-parallel** weights ``(c_out, c_in)`` put c_out on ``model``; the
  c_in axis is FSDP-sharded on ``data`` only when the leaf is large enough
  (> ``fsdp_min_size`` elements) for the gather to amortize.
* **row-parallel** weights (``w_down``/``wo``/``out_proj`` — the projections
  whose *input* is already model-sharded) put c_in on ``model`` and FSDP
  c_out on ``data``.
* **MoE expert** stacks ``(L, E, c_out, c_in)`` put experts on ``model``
  (expert parallelism) and c_in on ``data``.
* **KV caches** ``(..., B, H, S, hd)`` put batch on ``data`` and heads on
  ``model`` (right-aligned so leading layer-stack axes replicate).
* everything that matches no rule (NAS gammas, norms, scales, biases,
  scalars) replicates.

Every assignment passes a **divisibility gate**: an axis whose extent the
mesh-axis size does not divide falls back to replicated on that axis (the
Megatron vocab-padding story makes the fallback rare in practice), and the
decision is recorded in ``self.decisions`` for ``explain()``.

``constrain`` is the in-model activation annotation: a no-op unless an
``activation_sharding(mesh)`` context is active, so pure-CPU tests and
single-device smoke runs never touch collectives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Axis tokens used by the in-model ``constrain`` calls.
_AXIS_OF = {"D": "data", "M": "model", None: None}

_state = threading.local()


@contextlib.contextmanager
def activation_sharding(mesh: Mesh):
    """Enable ``constrain`` annotations for code run inside this context."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


@contextlib.contextmanager
def serving_mesh(ctx: "MeshContext"):
    """Enable serving-side mesh annotations (``replicate_serving`` and the
    QTensor TP/EP kernel routing) for code traced inside this context.

    Deliberately separate from ``activation_sharding``: the training-side
    ``constrain`` annotations in the shared attention core stay inert while
    the serving engine traces with a mesh.
    """
    prev = getattr(_state, "serving_ctx", None)
    _state.serving_ctx = ctx if (ctx is not None and ctx.is_active) else None
    try:
        yield
    finally:
        _state.serving_ctx = prev


def serving_ctx() -> Optional["MeshContext"]:
    """The active serving ``MeshContext``, or None outside ``serving_mesh``."""
    return getattr(_state, "serving_ctx", None)


def replicated_kernel(fn):
    """``fn`` run whole on every device of the active serving mesh.

    The TPU compiler cannot partition a Pallas (Mosaic) kernel: under a
    multi-device mesh every kernel call must sit inside a ``shard_map``.
    The operands of the kernels that ``serving_shardings`` leaves
    replicated (per-group GEMMs, unsharded fused buffers, decode attention
    over the replicated ring views) go in replicated, and each device runs
    the same launch — the single-device result on every device.  Identity
    outside ``serving_mesh``.
    """
    ctx = serving_ctx()
    if ctx is None:
        return fn
    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def replicate_serving(x):
    """Pin ``x`` replicated across the active serving mesh.

    Identity when no ``serving_mesh`` context is active, so model code can
    annotate unconditionally — single-device serving traces are unchanged.
    Used on every f32-adjacent activation (attention views, router inputs)
    whose reduction order must not depend on the mesh.
    """
    ctx = serving_ctx()
    if ctx is None or x is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.replicated)


def constrain(x, *tokens):
    """Annotate intermediate ``x`` with a (data/model) layout.

    ``tokens`` are per-axis: "D" -> data, "M" -> model, None -> replicated.
    Outside an ``activation_sharding`` context this is the identity, so model
    code can annotate unconditionally.
    """
    mesh = getattr(_state, "mesh", None)
    if mesh is None:
        return x
    assert len(tokens) == x.ndim, (tokens, x.shape)
    spec = []
    for tok, extent in zip(tokens, x.shape):
        ax = _AXIS_OF[tok]
        if ax is not None and extent % mesh.shape[ax] == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


@dataclasses.dataclass
class Decision:
    path: str
    shape: tuple
    spec: P
    note: str


# Names whose *input* axis is model-sharded (output of a column-parallel
# projection feeds them): shard c_in on model, c_out on data (FSDP).
_ROW_PARALLEL = ("w_down", "wo", "out_proj")
# Stacked MoE expert weights: (L, E, c_out, c_in).
_EXPERT = ("we_gate", "we_up", "we_down")
# KV-cache leaves: (stack..., B, H, S, hd).
_CACHE_LEAVES = ("k", "v", "ckv", "krope", "k_scale", "v_scale", "ckv_scale")


class ShardingRules:
    """Path-pattern -> PartitionSpec engine for one mesh."""

    def __init__(self, mesh: Mesh, fsdp: bool = True, moe_ep2d: bool = False,
                 kv_seq_shard: bool = False, fsdp_min_size: int = 1 << 20):
        self.mesh = mesh
        self.fsdp = fsdp
        self.moe_ep2d = moe_ep2d          # experts across model *and* data
        self.kv_seq_shard = kv_seq_shard  # shard cache seq axis on data
        self.fsdp_min_size = fsdp_min_size
        self.decisions: list[Decision] = []

    # Token-level axis size; tests monkeypatch this to simulate big meshes.
    def _axis_size(self, tok: str) -> int:
        return self.mesh.shape[_AXIS_OF[tok]]

    def _gate(self, tokens: Sequence[Optional[str]], shape, notes: list):
        """Divisibility gate: replicate any axis the mesh does not divide."""
        out = []
        for tok, extent in zip(tokens, shape):
            if tok is None:
                out.append(None)
                continue
            size = self._axis_size(tok)
            if extent % size:
                notes.append(f"dim {extent} % {_AXIS_OF[tok]}={size} != 0 "
                             f"-> replicate")
                out.append(None)
            else:
                out.append(_AXIS_OF[tok])
        return out

    def _leaf_tokens(self, path: str, shape) -> tuple[list, str]:
        """Raw (pre-gate) axis tokens for one leaf, plus the rule name."""
        parts = path.split("/")
        leaf = parts[-1]
        parent = parts[-2] if len(parts) > 1 else ""
        big = 1
        for d in shape:
            big *= d
        fsdp_on = self.fsdp and big >= self.fsdp_min_size

        in_cache = "caches" in parts or leaf in _CACHE_LEAVES
        if in_cache and len(shape) >= 4:
            # right-aligned (B, H, S, hd); leading stack axes replicate
            toks = [None] * (len(shape) - 4)
            toks += ["D", "M", "D" if self.kv_seq_shard else None, None]
            return toks, "kv-cache"

        is_weight = leaf in ("w", "packed", "scale", "embed") or \
            parent in _EXPERT or parent in _ROW_PARALLEL or \
            any(n in parts for n in ("lm_head", "embed"))
        if leaf in ("gamma", "delta", "aw", "ax") or len(shape) <= 1:
            return [None] * len(shape), "replicate (nas/small)"

        # MoE routers run their top-k in f32; sharding that GEMM changes
        # the CPU reduction order and breaks token-for-token parity, so the
        # (E, d) router weight always replicates.
        if leaf == "router":
            return [None] * len(shape), "replicate (f32 router determinism)"

        # QTensor (repro.api.qtensor) leaves: packed rows carry the deployed
        # output channels -> model axis; scales follow their rows.
        if "packed" in parts and len(shape) >= 2:
            return [None] * (len(shape) - 2) + ["M", None], "qtensor-packed"
        if ("scales" in parts or leaf == "scale") and len(shape) >= 1:
            # per-channel dequant steps: rows axis is LAST
            return [None] * (len(shape) - 1) + ["M"], "qtensor-scale"
        if "inv_perm" in parts:
            return [None] * len(shape), "replicate (perm)"

        # MoE expert stacks: (E, c_out, c_in) or (L, E, c_out, c_in)
        if any(n in parts for n in _EXPERT) and len(shape) >= 3:
            toks = [None] * (len(shape) - 3)
            toks += ["M", None, "D" if fsdp_on else None]
            if self.moe_ep2d:
                toks[-3] = "M"
            return toks, "moe-expert"

        if is_weight and len(shape) >= 2:
            row = any(n in parts for n in _ROW_PARALLEL)
            lead = [None] * (len(shape) - 2)
            if row:
                return lead + ["D" if fsdp_on else None, "M"], "row-parallel"
            return lead + ["M", "D" if fsdp_on else None], "column-parallel"

        return [None] * len(shape), "replicate (default)"

    def spec_for(self, path: str, shape) -> P:
        toks, rule = self._leaf_tokens(path, tuple(shape))
        notes: list[str] = []
        axes = self._gate(toks, shape, notes)
        spec = P(*axes)
        self.decisions.append(Decision(path, tuple(shape), spec,
                                       "; ".join([rule] + notes)))
        return spec

    def _fused_spec(self, path: str, leaf: str, shape, qt) -> P:
        """Sharding for a QTensor's fused ragged buffer / scale vector.

        The fused layout concatenates whole static-bit N-tiles, so the only
        legal shard boundary is a tile boundary:

        * tensor parallel (single / layer-stacked weights): shard the last
          axis (byte columns of the buffer, channels of the scales) iff
          the tile schedule splits into ``model`` identical chunks
          (``quant_matmul.tp_chunk``) — each device then owns whole tiles
          and runs the same shard_map program;
        * expert parallel (expert-stacked weights): shard the leading E axis
          iff ``model`` divides E (the schedule is shared across experts);
        * otherwise replicate and record why.
        """
        from repro.kernels import quant_matmul as qm
        m = self._axis_size("M")
        axes = [None] * len(shape)
        note = "replicate (fused: model axis = 1)"
        if m > 1 and qt.tile_bits is not None:
            if qt.experts is not None:
                e_ax = len(shape) - 1 - qm.FUSED_LEAF_RANK[leaf]
                if e_ax >= 0 and shape[e_ax] % m == 0:
                    axes[e_ax] = "model"
                    note = f"qtensor-fused-ep (E={shape[e_ax]} / model={m})"
                else:
                    note = f"replicate (fused: E !% model={m})"
            else:
                chunk = qm.tp_chunk(qt.tile_bits, m)
                if chunk is not None and shape[-1] % m == 0:
                    axes[-1] = "model"
                    note = f"qtensor-fused-tp (chunk={chunk})"
                else:
                    note = ("replicate (fused: tile schedule not periodic "
                            f"over model={m})")
        spec = P(*axes)
        self.decisions.append(Decision(f"{path}/{leaf}", tuple(shape), spec,
                                       note))
        return spec

    def qtensor_shardings(self, path: str, qt):
        """Per-leaf NamedShardings for one QTensor node (same pytree shape).

        Non-fused leaves route through the ordinary path rules; the fused
        ragged buffer and its scales get the tile-schedule-aware treatment
        of ``_fused_spec``.
        """
        flat, treedef = jax.tree_util.tree_flatten_with_path(qt)
        out = []
        for key_path, leaf in flat:
            sub = "/".join(_key_str(k) for k in key_path)
            name = _key_str(key_path[0]) if key_path else ""
            shape = tuple(getattr(leaf, "shape", ()))
            if name in ("fused_packed", "fused_scales"):
                spec = self._fused_spec(path, name, shape, qt)
            else:
                # grouped buckets / permutations feed the jnp dequant GEMM,
                # whose f32 matmul is not shard-invariant on CPU — keep them
                # replicated so the mesh engine stays token-identical (the
                # fused leaves above are the sharded, shard_map-exact path)
                spec = P(*([None] * len(shape)))
                self.decisions.append(Decision(
                    f"{path}/{sub}", shape, spec,
                    "replicate (qtensor dequant path: f32 GEMM "
                    "determinism)"))
            out.append(NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_unflatten(treedef, out)

    def tree_shardings(self, tree):
        """NamedSharding pytree matching ``tree`` (arrays or SDStructs).

        QTensor nodes are intercepted whole so their fused buffers can be
        sharded along the N-tile schedule (``qtensor_shardings``); plain
        array leaves map through ``spec_for`` as before.
        """
        try:
            from repro.api.qtensor import QTensor
        except Exception:                                  # pragma: no cover
            QTensor = ()

        def is_qt(node):
            return isinstance(node, QTensor) if QTensor else False

        def one(key_path, node):
            path = "/".join(_key_str(k) for k in key_path)
            if is_qt(node):
                return self.qtensor_shardings(path, node)
            shape = getattr(node, "shape", ())
            return NamedSharding(self.mesh, self.spec_for(path, shape))
        return jax.tree_util.tree_map_with_path(one, tree, is_leaf=is_qt)

    def serving_shardings(self, tree):
        """Deployment placement for the mesh serving engine.

        The serving contract is **token identity** with the single-device
        engine, so only operands whose sharded compute is provably
        bit-exact may shard: a QTensor's fused buffers (the shard_map
        integer kernels partition whole N-tiles / whole experts and are
        bitwise-identical to the unsharded launch).  Every other weight
        replicates — CPU f32/bf16 GEMMs are not shard-invariant, and a
        sharded norm scale or dequant bucket would silently re-shard the
        activations feeding them.
        """
        try:
            from repro.api.qtensor import QTensor
        except Exception:                                  # pragma: no cover
            QTensor = ()

        def is_qt(node):
            return isinstance(node, QTensor) if QTensor else False

        rep = NamedSharding(self.mesh, P())

        def one(key_path, node):
            path = "/".join(_key_str(k) for k in key_path)
            if is_qt(node):
                return self.qtensor_shardings(path, node)
            shape = tuple(getattr(node, "shape", ()))
            self.decisions.append(Decision(
                path, shape, P(), "replicate (serving token-identity)"))
            return rep
        return jax.tree_util.tree_map_with_path(one, tree, is_leaf=is_qt)

    def explain(self) -> str:
        lines = [f"{d.path}  {d.shape} -> {d.spec}   [{d.note}]"
                 for d in self.decisions]
        return "\n".join(lines)


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return str(k.name)
    if isinstance(k, jax.tree_util.FlattenedIndexKey):
        return str(k.key)
    return str(k)


class MeshContext:
    """One mesh handle threaded through the whole serving stack.

    ``mesh=None`` (the default everywhere) makes every method the identity:
    single-device serving runs exactly the pre-mesh code path, bit-for-bit,
    and nothing below ever touches a collective.

    With a live ``(data, model)`` mesh the context owns the placement
    contract:

    * ``put_params``      — weights via ``ShardingRules`` (QTensor-aware);
    * ``put_caches`` / ``constrain_caches`` — KV pools and page tables
      sharded along the slot/page axis (axis 1) on ``data``;
    * ``put_replicated`` / ``constrain_replicated`` — scheduler state,
      tokens, and sampling stay replicated;
    * ``data`` / ``model`` — axis sizes (1 when inactive), which double as
      the host count for the fault/drain story.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None):
        if mesh is not None:
            names = tuple(mesh.axis_names)
            if "data" not in names or "model" not in names:
                raise ValueError(
                    f"serving mesh needs ('data', 'model') axes, got {names}")
        self.mesh = mesh
        self.rules = rules if rules is not None else (
            ShardingRules(mesh) if mesh is not None else None)

    @property
    def is_active(self) -> bool:
        return self.mesh is not None

    @property
    def data(self) -> int:
        return int(self.mesh.shape["data"]) if self.is_active else 1

    @property
    def model(self) -> int:
        return int(self.mesh.shape["model"]) if self.is_active else 1

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- placement (host-side device_put; identity when inactive) ----------
    def put_params(self, tree):
        if not self.is_active or tree is None:
            return tree
        return jax.device_put(tree, self.rules.serving_shardings(tree))

    def put_replicated(self, tree):
        if not self.is_active or tree is None:
            return tree
        rep = self.replicated
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), tree)

    def cache_shardings(self, tree):
        """Axis 1 (the slot or physical-page axis of every cache leaf —
        dense rings, paged pools, page tables alike) on ``data`` when
        divisible; replicated otherwise."""
        d = self.data

        def one(leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            if len(shape) >= 2 and d > 1 and shape[1] % d == 0:
                return NamedSharding(self.mesh, P(None, "data"))
            return self.replicated
        return jax.tree_util.tree_map(one, tree)

    def put_caches(self, tree):
        if not self.is_active or tree is None:
            return tree
        return jax.device_put(tree, self.cache_shardings(tree))

    # -- trace-time constraints (identity when inactive) --------------------
    def constrain_caches(self, tree):
        if not self.is_active or tree is None:
            return tree
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, self.cache_shardings(tree))

    def constrain_replicated(self, tree):
        if not self.is_active or tree is None:
            return tree
        rep = self.replicated
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), tree)


def batch_specs(mesh: Mesh, batch):
    """Data-parallel shardings for one host batch: leading axis on ``data``
    when divisible, else replicated."""
    data = mesh.shape["data"]

    def one(leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 1 and shape[0] % data == 0:
            return NamedSharding(mesh, P("data", *([None] * (len(shape) - 1))))
        return NamedSharding(mesh, P(*([None] * len(shape))))
    return jax.tree_util.tree_map(one, batch)
