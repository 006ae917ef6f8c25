"""Mesh-aware serving: token identity, sharded kernels, host drain.

The mesh serving contract (PR 9) is **token identity**: a ServingEngine
constructed with a ``(data, model)`` mesh must emit exactly the tokens the
single-device engine emits on the same trace — ``mesh=None``, a trivial
``(1, 1)`` mesh and an 8-way ``(2, 4)`` mesh are all interchangeable, on
both the jnp and pallas backends.  The placement that makes this possible
(``ShardingRules.serving_shardings``): only operands whose sharded compute
is bitwise-exact may shard — a QTensor's fused buffers through the
shard_map integer kernels (whole N-tiles / whole experts per device),
caches along their slot/page axis — while every float GEMM weight
replicates (CPU f32 matmuls are not shard-invariant).

The 8-device cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
**and** ``REPRO_KEEP_XLA_FLAGS=1`` (tests/conftest.py otherwise strips the
flag); without them they skip and the 1-device subset still runs.

Also here: the heartbeat-driven host-drain path (a dead data-axis host's
slots requeue and every request still completes with the exact baseline
tokens) and the ``count_pallas_launches`` shard_map/pjit walk guard.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.api.qtensor import QTensor
from repro.api.scheduler import Request, ServingEngine
from repro.config import get_config
from repro.dist import sharding as shd
from repro.kernels import ops
from repro.kernels import quant_matmul as qmk
from repro.models import serving

N_DEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 CPU devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8 + REPRO_KEEP_XLA_FLAGS=1)")

_CFG_CACHE = {}


def _setup(arch, seed=0):
    if arch not in _CFG_CACHE:
        cfg = get_config(arch).reduced()
        dp = serving.init_deployed_model(cfg, jax.random.PRNGKey(seed))
        _CFG_CACHE[arch] = (cfg, dp)
    return _CFG_CACHE[arch]


def _mesh(data, model):
    n = data * model
    return Mesh(np.asarray(jax.devices()[:n]).reshape(data, model),
                ("data", "model"))


def _trace(cfg, lens, mts, seed=2):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size, (l,)).astype(np.int32),
                    max_tokens=m) for l, m in zip(lens, mts)]


STAG = dict(lens=(8, 6, 7, 5), mts=(10, 3, 6, 4), arrivals=(0, 0, 2, 5),
            P=8, M=24, B=2)
STAG_SMALL = dict(lens=(6, 4, 5), mts=(4, 2, 3), arrivals=(0, 0, 2),
                  P=8, M=16, B=2)


def _run(cfg, dp, backend, spec, mesh=None, max_slots=None):
    eng = ServingEngine(cfg, dp, backend=backend,
                        max_slots=max_slots or spec["B"],
                        max_len=spec["M"], prefill_len=spec["P"], mesh=mesh)
    outs = eng.run(_trace(cfg, spec["lens"], spec["mts"]), spec["arrivals"])
    return eng, {i: np.asarray(outs[i].tokens)
                 for i in range(len(spec["lens"]))}


# ---------------------------------------------------------------------------
# Token identity: mesh=(1,1) (runs on any host) and 8-way (2,4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v3-671b"])
def test_mesh1_token_identical(arch):
    """A trivial (1, 1) mesh engine is bit-for-bit the meshless engine."""
    cfg, dp = _setup(arch)
    _, base = _run(cfg, dp, "jnp", STAG)
    _, m1 = _run(cfg, dp, "jnp", STAG, mesh=_mesh(1, 1))
    for i in base:
        np.testing.assert_array_equal(base[i], m1[i],
                                      err_msg=f"{arch} request {i}")


@needs8
@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v3-671b"])
def test_mesh8_token_identical_jnp(arch):
    """8-way (data=2, model=4) jnp engine == single-device engine on a
    staggered trace (dense and moe+mla)."""
    cfg, dp = _setup(arch)
    _, base = _run(cfg, dp, "jnp", STAG)
    _, m8 = _run(cfg, dp, "jnp", STAG, mesh=_mesh(2, 4))
    for i in base:
        np.testing.assert_array_equal(base[i], m8[i],
                                      err_msg=f"{arch} request {i}")


@needs8
@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v3-671b"])
def test_mesh8_token_identical_pallas(arch):
    """Same 8-way identity through the fused Pallas kernels — deepseek's
    expert stacks route through the shard_map EP kernel (E=4 over
    model=4), qwen's non-periodic tile schedules fall back to the
    replicated fused launch (both must land on the same tokens)."""
    cfg, dp = _setup(arch)
    _, base = _run(cfg, dp, "pallas", STAG_SMALL)
    _, m8 = _run(cfg, dp, "pallas", STAG_SMALL, mesh=_mesh(2, 4))
    for i in base:
        np.testing.assert_array_equal(base[i], m8[i],
                                      err_msg=f"{arch} request {i}")


@needs8
def test_mesh8_zero_recompiles_after_warmup():
    """The mesh engine keeps the fixed-shape launch contract: after the
    first trace warms the jit caches, serving more requests must not grow
    them."""
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=STAG["B"],
                        max_len=STAG["M"], prefill_len=STAG["P"],
                        mesh=_mesh(2, 4))
    eng.run(_trace(cfg, STAG["lens"], STAG["mts"]), STAG["arrivals"])
    warm = eng.compile_counts()
    eng.run(_trace(cfg, STAG["lens"], STAG["mts"], seed=7),
            STAG["arrivals"])
    assert eng.compile_counts() == warm


# ---------------------------------------------------------------------------
# Host failure: heartbeat-declared death drains slots, trace completes
# ---------------------------------------------------------------------------

@needs8
def test_host_drain_mid_trace_token_identical():
    """Killing one data-axis host mid-trace drains its slots back into the
    admission queue; every request still completes, with tokens exactly
    equal to the unfailed baseline (greedy replay determinism)."""
    cfg, dp = _setup("qwen1.5-4b")
    lens, mts = (8, 6, 7, 5), (10, 8, 9, 7)
    reqs = _trace(cfg, lens, mts)

    def drive(mesh=None, fail_host=None):
        eng = ServingEngine(cfg, dp, backend="jnp", max_slots=4,
                            max_len=24, prefill_len=8, mesh=mesh)
        rids = [eng.submit(r) for r in reqs]
        outs, t = {}, 0
        while eng.has_work():
            if fail_host is not None and t == 1:
                eng.fail_host(fail_host)
            eng.step()
            for o in eng.collect():
                outs[o.rid] = o
            t += 1
        return eng, {r: np.asarray(outs[r].tokens) for r in rids}

    _, base = drive()
    eng, failed = drive(mesh=_mesh(2, 4), fail_host=1)
    assert eng.stats["host_drains"] == 1
    assert eng.stats["drained_requests"] > 0
    assert len(failed) == len(reqs)          # every request completed
    # host 1's slots are retired from admission
    from repro.dist import fault
    for s in fault.owned_slots(1, 4, 2):
        assert eng._dead_slots[s] and eng._slots[s] is None
    for r in base:
        np.testing.assert_array_equal(base[r], failed[r],
                                      err_msg=f"request {r} diverged "
                                              "after host drain")


def test_fail_host_validates_range():
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2, max_len=16,
                        prefill_len=8)
    with pytest.raises(ValueError):
        eng.fail_host(1)                     # meshless fleet has 1 host


# ---------------------------------------------------------------------------
# Sharded fused kernels: bitwise identity with the unsharded launch
# ---------------------------------------------------------------------------

def _uniform_qtensor(seed, c_out, c_in, bits, tile_n):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((c_out, c_in)).astype(np.float32)
    alpha = np.abs(w).max(-1)
    return QTensor.from_assignment(w, np.full(c_out, bits), alpha,
                                   tile_n=tile_n)


def test_tp_chunk_periodicity():
    """Shard gate: a schedule splits iff it is ``parts`` identical chunks."""
    assert qmk.tp_chunk((8, 8, 8, 8), 4) == (8,)
    assert qmk.tp_chunk((8, 4, 8, 4), 2) == (8, 4)
    assert qmk.tp_chunk((8, 8, 4, 4), 2) is None      # sorted, not periodic
    assert qmk.tp_chunk((8, 4, 8), 2) is None         # odd tile count
    assert qmk.tp_chunk((8, 4), 1) is None            # no model parallelism
    assert qmk.tp_chunk(None, 4) is None


@needs8
def test_fused_tp_bitwise_identical():
    """shard_map TP fused GEMM == the unsharded single launch, bit for bit
    (each device runs the same int kernel over its own whole tiles)."""
    qt = _uniform_qtensor(3, 64, 32, 8, tile_n=16)    # schedule (8,8,8,8)
    mesh = _mesh(1, 4)
    chunk = qmk.tp_chunk(qt.tile_bits, 4)
    assert chunk is not None
    x = jnp.asarray(np.random.default_rng(5).standard_normal((9, 32)),
                    jnp.float32)
    y_ref = ops.quant_matmul_fused(
        x, qt.fused_packed, qt.fused_scales, qt.fused_perm, qt.tile_bits,
        qt.tile_n, qt.c_in, qt.c_out)
    y_tp = ops.quant_matmul_fused_tp(
        x, qt.fused_packed, qt.fused_scales, qt.fused_perm, qt.tile_bits,
        chunk, qt.tile_n, qt.c_in, qt.c_out, mesh)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_tp))
    # and QTensor.matmul routes there by itself inside a serving context
    ctx = shd.MeshContext(mesh)
    with shd.serving_mesh(ctx):
        y_auto = qt.matmul(x, jnp.float32, backend="pallas")
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_auto))


@needs8
def test_fused_ep_bitwise_identical():
    """shard_map EP expert-batched GEMM == the unsharded 3-D launch."""
    E, c_out, c_in = 4, 40, 16
    cfg = get_config("deepseek-v3-671b").reduced()
    dp = serving.init_deployed_linear(jax.random.PRNGKey(7), c_in, c_out,
                                      cfg, expert_axis=E)
    qt = dp["w"]
    assert qt.experts == E
    x = jnp.asarray(np.random.default_rng(9).standard_normal((E, 8, c_in)),
                    jnp.float32)
    y_ref = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
    ctx = shd.MeshContext(_mesh(2, 4))
    with shd.serving_mesh(ctx):                       # E=4 % model=4 == 0
        y_ep = np.asarray(qt.matmul(x, jnp.float32, backend="pallas"))
    np.testing.assert_array_equal(y_ref, y_ep)


# ---------------------------------------------------------------------------
# Placement rules
# ---------------------------------------------------------------------------

def test_serving_shardings_replicate_everything_but_fused():
    """Serving placement: QTensor fused buffers may shard (tile schedule /
    expert axis permitting), every other leaf — norm scales, biases,
    embeddings, dequant buckets — replicates, and the decision log says
    why."""
    cfg, dp = _setup("qwen1.5-4b")
    rules = shd.ShardingRules(_mesh(1, 1))
    sh = rules.serving_shardings(dp)
    flat, _ = jax.tree_util.tree_flatten_with_path(dp)
    specs = jax.tree_util.tree_leaves(
        sh, is_leaf=lambda s: hasattr(s, "spec"))
    assert len(flat) == len(specs)
    for (path, _), s in zip(flat, specs):
        name = jax.tree_util.keystr(path)
        if "fused_packed" not in name and "fused_scales" not in name:
            assert all(a is None for a in s.spec), (name, s.spec)
    notes = " ".join(d.note for d in rules.decisions)
    assert "serving token-identity" in notes
    assert "qtensor" in notes or "fused" in notes


def test_mesh_context_validates_axes():
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    with pytest.raises(ValueError):
        shd.MeshContext(Mesh(devs, ("x", "y")))
    assert not shd.MeshContext(None).is_active
    assert shd.MeshContext(None).data == 1 and shd.MeshContext(None).model == 1


@needs8
def test_cache_shardings_slot_axis():
    """Cache leaves shard axis 1 (slot/page) on data when it divides;
    non-divisible extents and low-rank leaves replicate."""
    ctx = shd.MeshContext(_mesh(2, 4))
    tree = {"k": jnp.zeros((2, 4, 8)), "odd": jnp.zeros((2, 5, 8)),
            "pos": jnp.zeros((3,))}
    sh = ctx.cache_shardings(tree)
    assert sh["k"].spec == P(None, "data")
    assert sh["odd"].spec == P()             # 5 % data=2 != 0
    assert sh["pos"].spec == P()
    # a 1-wide data axis never bothers sharding
    assert shd.MeshContext(_mesh(1, 1)).cache_shardings(tree)["k"].spec == P()


# ---------------------------------------------------------------------------
# count_pallas_launches walks into shard_map / pjit bodies
# ---------------------------------------------------------------------------

def test_count_launches_through_shard_map_and_pjit():
    """The launch counter must see kernels hidden under shard_map and
    nested-jit (pjit) sub-jaxprs — one program-level count each."""

    qt = _uniform_qtensor(11, 32, 16, 8, tile_n=16)
    x = jnp.zeros((4, 16), jnp.float32)

    def fused(xv):
        return ops.quant_matmul_fused(
            xv, qt.fused_packed, qt.fused_scales, qt.fused_perm,
            qt.tile_bits, qt.tile_n, qt.c_in, qt.c_out)

    assert ops.count_pallas_launches(fused, x) == 1
    # under an explicit nested jit (pjit eqn in the outer jaxpr)
    assert ops.count_pallas_launches(jax.jit(fused), x) == 1

    mesh = _mesh(1, 1)

    def sharded(xv):
        return jax.shard_map(fused, mesh=mesh, in_specs=(P(),),
                             out_specs=P(), check_vma=False)(xv)

    assert ops.count_pallas_launches(sharded, x) == 1
    # two launches under one shard_map still count as two
    def sharded2(xv):
        return jax.shard_map(lambda v: fused(v) + fused(v), mesh=mesh,
                             in_specs=(P(),), out_specs=P(),
                             check_vma=False)(xv)

    assert ops.count_pallas_launches(sharded2, x) == 2
