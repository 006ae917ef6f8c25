"""Ragged-vs-lockstep parity for the request-level serving engine.

The continuous-batching redesign (api/scheduler.py) must not change a
single token: with equal-length synchronized requests ``ServingEngine.run``
is a lockstep prefill+decode loop over the shared ``engine.serving_jits``
executables, its decode launches operand-for-operand and its admission
the same row-independent prefill one request per launch, so its tokens
must be **bit-identical**; on staggered traces every request must decode
as if it were alone in the pool (per-slot positions + live masks isolate slots), so
each output must match a per-request lockstep generate token-for-token and
be independent of co-scheduled slot contents.  The engines here run the
default **paged** KV cache (PR 6) where the family supports it — the
dense-vs-paged bit-parity guards live in tests/test_paged_cache.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api.engine as engine_mod
from repro.api.engine import serving_jits
from repro.api.sampling import GREEDY, SamplingParams, sample
from repro.api.scheduler import Request, ServingEngine
from repro.config import get_config
from repro.models import serving

_CFG_CACHE = {}


def _setup(arch, seed=0, **overrides):
    """Config + deployed params, cached so every test (and the module-level
    serving jit caches keyed on cfg id) shares one instance per arch."""
    key = (arch, seed, tuple(sorted(overrides.items())))
    if key not in _CFG_CACHE:
        cfg = get_config(arch).reduced()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        dp = serving.init_deployed_model(cfg, jax.random.PRNGKey(seed))
        _CFG_CACHE[key] = (cfg, dp)
    return _CFG_CACHE[key]


def _lockstep_generate(cfg, dp, batch, gen, max_len, backend="jnp",
                       sampling=GREEDY, key=None, prefill_rows=False):
    """Lockstep oracle: one shared prefill, then ``gen`` synchronized
    decode steps over the module-cached ``serving_jits`` executables —
    the ~10-line loop that replaced the removed ``ServingSession``.
    ``prefill_rows`` prefills each row alone, as the engine's admission
    does.  Returns tokens (B, gen+1) including the prefill-sampled one."""
    fns = serving_jits(cfg, backend)
    B, S = batch["tokens"].shape
    if sampling.kind != "greedy" and key is None:
        key = jax.random.PRNGKey(0)
    if prefill_rows:
        rows = [fns["prefill"](dp, {k: v[i:i + 1] for k, v in batch.items()})
                for i in range(B)]
        logits = jnp.concatenate([lg for lg, _ in rows])
        pf = jax.tree_util.tree_map(lambda *t: jnp.concatenate(t, axis=1),
                                    *[c for _, c in rows])
    else:
        logits, pf = fns["prefill"](dp, batch)
    caches = serving.embed_caches(pf, serving.init_caches(cfg, B, max_len))
    if key is not None:
        key, k0 = jax.random.split(key)
    tokens = sample(logits[:, -1:], sampling, None if key is None else k0)
    out = [tokens]
    for i in range(gen):
        pos = jnp.full((B,), S + i, jnp.int32)
        logits, caches = fns["decode"](dp, tokens, caches, pos)
        if key is not None:
            key, ki = jax.random.split(key)
        tokens = sample(logits[:, -1:], sampling,
                        None if key is None else ki)
        out.append(tokens)
    return jnp.concatenate(out, axis=1)


def _prompts(cfg, shape, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, shape).astype(np.int32)


# ---------------------------------------------------------------------------
# Equal-length synchronized requests: bit-identical to the lockstep loop
# ---------------------------------------------------------------------------

SYNC_CASES = [
    ("qwen1.5-4b", "jnp"),          # dense
    ("deepseek-v3-671b", "jnp"),    # moe + mla
    ("mamba2-780m", "jnp"),         # ssm
    ("qwen1.5-4b", "pallas"),       # dense through the fused kernels
]


@pytest.mark.parametrize("arch,backend", SYNC_CASES)
def test_sync_requests_bit_identical_to_lockstep(arch, backend):
    cfg, dp = _setup(arch)
    B, S, G = (2, 4, 3) if backend == "pallas" else (2, 8, 6)
    toks = _prompts(cfg, (B, S), seed=1)
    # MoE couples co-batched rows through expert-capacity drops, so its
    # oracle prefills each row alone, as admission does; the other
    # families' rows are independent and the shared prefill gives the
    # same bits
    ref = _lockstep_generate(cfg, dp, {"tokens": jnp.asarray(toks)},
                             gen=G - 1, max_len=S + G, backend=backend,
                             prefill_rows=cfg.family == "moe")
    eng = ServingEngine(cfg, dp, backend=backend, max_slots=B,
                        max_len=S + G, prefill_len=S)
    outs = eng.run([Request(toks[i], max_tokens=G) for i in range(B)])
    assert eng.stats["admit_ticks"] == 1        # one shared admission tick
    assert eng.stats["prefill_launches"] == B   # one launch per request
    for i in range(B):
        np.testing.assert_array_equal(outs[i].tokens, np.asarray(ref[i]))


# ---------------------------------------------------------------------------
# Staggered arrivals: every request matches its own per-request generate
# ---------------------------------------------------------------------------

STAGGER = dict(lens=(8, 6, 7, 5), mts=(10, 3, 6, 4), arrivals=(0, 0, 2, 5),
               P=8, M=24, B=2)


def _stagger_trace(cfg, seed):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size, (l,)).astype(np.int32),
                    max_tokens=m)
            for l, m in zip(STAGGER["lens"], STAGGER["mts"])]


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "mamba2-780m",
                                  "deepseek-v3-671b"])
def test_staggered_matches_per_request_generate(arch):
    # MoE couples co-batched rows only through expert-capacity overflow
    # drops; a large capacity_factor removes drops (capacity == tokens), so
    # routing stays per-token and the slot-isolation contract is testable.
    over = ({"capacity_factor": 64.0} if arch == "deepseek-v3-671b" else {})
    cfg, dp = _setup(arch, **over)
    reqs = _stagger_trace(cfg, seed=2)
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=STAGGER["B"],
                        max_len=STAGGER["M"], prefill_len=STAGGER["P"])
    outs = eng.run(reqs, STAGGER["arrivals"])
    for i, r in enumerate(reqs):
        ref = _lockstep_generate(cfg, dp,
                                 {"tokens": jnp.asarray(r.tokens)[None]},
                                 gen=r.max_tokens - 1, max_len=STAGGER["M"])
        np.testing.assert_array_equal(
            outs[i].tokens, np.asarray(ref[0]),
            err_msg=f"request {i} diverged from its per-request lockstep "
                    "generate")
        assert outs[i].finish_reason == "length"


def test_staggered_outputs_independent_of_coscheduled_slots():
    """The same request must produce the same tokens no matter what shares
    the pool with it: different co-requests, arrival patterns and queueing
    pressure may not leak into a slot (per-slot masks + page tables)."""
    cfg, dp = _setup("qwen1.5-4b")
    probe = Request(_prompts(cfg, (7,), seed=3), max_tokens=8)

    def run_with(others, arrivals):
        eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                            max_len=24, prefill_len=8)
        outs = eng.run([probe] + others, arrivals)
        return outs[0].tokens

    alone = run_with([], [0])
    rng = np.random.default_rng(4)
    for seed, arrivals in ((5, [0, 0, 1]), (6, [0, 2, 3])):
        others = [Request(rng.integers(0, cfg.vocab_size,
                                       (int(rng.integers(1, 9)),)
                                       ).astype(np.int32),
                          max_tokens=int(rng.integers(2, 10)))
                  for _ in range(2)]
        np.testing.assert_array_equal(alone, run_with(others, arrivals))


def test_eos_frees_slot_early():
    cfg, dp = _setup("qwen1.5-4b")
    reqs = _stagger_trace(cfg, seed=2)
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                        max_len=24, prefill_len=8)
    base = eng.run(reqs, STAGGER["arrivals"])[0].tokens
    assert len(base) >= 4
    eos = int(base[3])
    reqs = _stagger_trace(cfg, seed=2)
    reqs[0] = dataclasses.replace(reqs[0], eos_id=eos)
    eng2 = ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                         max_len=24, prefill_len=8)
    outs = eng2.run(reqs, STAGGER["arrivals"])
    np.testing.assert_array_equal(outs[0].tokens, base[:4])
    assert outs[0].finish_reason == "eos"
    # the freed slot really was reclaimed early
    assert eng2.stats["decode_launches"] <= eng.stats["decode_launches"]


# ---------------------------------------------------------------------------
# Launch/compile counters: slot reuse must never re-jit
# ---------------------------------------------------------------------------

def test_zero_recompiles_after_warmup():
    cfg, dp = _setup("qwen1.5-4b")
    mk = lambda: ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                               max_len=24, prefill_len=8)
    eng = mk()
    eng.run(_stagger_trace(cfg, seed=7), STAGGER["arrivals"])
    warm = eng.compile_counts()
    eng2 = mk()                                  # fresh engine, same shapes
    eng2.run(_stagger_trace(cfg, seed=8), [0, 1, 4, 6])
    assert eng2.stats["decode_launches"] > 0
    assert eng2.stats["prefill_launches"] >= 2   # slots really were refilled
    assert eng2.compile_counts() == warm, \
        "slot-pool serving recompiled after warmup"


# ---------------------------------------------------------------------------
# Admission: one (1, prefill_len) launch per admitted request
# ---------------------------------------------------------------------------

ADMIT_FAMILIES = [
    ("qwen1.5-4b", "auto"),         # dense, paged
    ("qwen1.5-4b", None),           # dense, dense rings
    ("deepseek-v3-671b", "auto"),   # moe + mla, paged
    ("mamba2-780m", None),          # ssm
    ("zamba2-1.2b", "auto"),        # hybrid: paged rings + per-slot state
]
DENSE_FAMILIES = ["qwen1.5-4b", "deepseek-v3-671b", "mamba2-780m",
                  "zamba2-1.2b"]
ADMIT = dict(max_slots=4, max_len=24, prefill_len=8)


_ADMIT_CFGS = {}


def _admit_engine(arch, page_size):
    """A 4-slot engine over a config object these tests alone use: the jit
    cache is keyed on cfg id, so its programs hold the ``ADMIT`` shapes
    only, and the compile counts other tests assert see none of them."""
    if arch not in _ADMIT_CFGS:
        cfg, dp = _setup(arch)
        _ADMIT_CFGS[arch] = (dataclasses.replace(cfg), dp)
    cfg, dp = _ADMIT_CFGS[arch]
    return ServingEngine(cfg, dp, backend="jnp", page_size=page_size,
                         **ADMIT)


def _leaves(tree):
    return [np.asarray(t).copy() for t in jax.tree_util.tree_leaves(tree)]


def _fill_two_slots(eng):
    """Slots 0 and 1 admitted and decoded once, so they hold state."""
    for n in (6, 4):
        eng.submit(Request(_prompts(eng.cfg, (n,), seed=n), max_tokens=8))
    assert eng.step()["kind"] == "prefill"
    assert eng.step()["kind"] == "decode"


@pytest.mark.parametrize("arch,page_size", ADMIT_FAMILIES)
def test_admission_tick_launches_once_per_request(arch, page_size):
    """A tick admitting three requests makes three (1, prefill_len)
    launches and one host sync, and the engine compiles one admission
    program."""
    eng = _admit_engine(arch, page_size)
    admit_fn, phase = eng._admit_fn, eng._phase
    launches, phases = [], []

    def spy(*args):
        launches.append(args[1]["tokens"].shape)
        return admit_fn(*args)

    eng._admit_fn = spy
    eng._phase = lambda p: phases.append(p) or phase(p)
    for n in (3, 8, 5):
        eng.submit(Request(_prompts(eng.cfg, (n,), seed=n), max_tokens=3))
    out = eng.step()
    assert out["kind"] == "prefill" and len(out["admitted"]) == 3
    assert launches == [(1, ADMIT["prefill_len"])] * 3
    assert phases == ["launch", "sync", "record"]
    assert eng.stats["admit_ticks"] == 1
    assert eng.stats["prefill_launches"] == 3
    assert eng.stats["prefill_tokens"] == 16
    assert eng.stats["prefill_rows"] == 3 * ADMIT["prefill_len"]
    assert eng.stats["prefill_slot_tokens"] == (ADMIT["max_slots"]
                                                * ADMIT["prefill_len"])
    eng._admit_fn, eng._phase = admit_fn, phase
    while eng.has_work():
        eng.step()
    assert [len(o.tokens) for o in eng.collect()] == [3, 3, 3]
    assert eng.compile_counts() == {"admit": 1, "step": 1}


@pytest.mark.parametrize("arch", DENSE_FAMILIES)
def test_admission_leaves_other_slots_untouched(arch):
    """Dense rings and SSM state: an admission into slot 2 leaves every
    other slot's cache leaves and pending token bit-identical."""
    eng = _admit_engine(arch, None)
    _fill_two_slots(eng)
    caches, tok = _leaves(eng.caches), np.asarray(eng.tokens).copy()
    eng.submit(Request(_prompts(eng.cfg, (7,), seed=7), max_tokens=4))
    assert eng.step()["kind"] == "prefill"
    assert eng._slots[2] is not None and eng._slots[3] is None
    other = [0, 1, 3]
    for a, b in zip(caches, _leaves(eng.caches)):
        np.testing.assert_array_equal(a[:, other], b[:, other])
    np.testing.assert_array_equal(tok[other], np.asarray(eng.tokens)[other])
    assert any(not np.array_equal(a[:, 2], b[:, 2])
               for a, b in zip(caches, _leaves(eng.caches)))


@pytest.mark.parametrize("arch", DENSE_FAMILIES)
def test_admitted_slot_equals_prefill_alone(arch):
    """The admitted slot holds exactly ``serving.prefill`` of its prompt
    alone, embedded into a one-slot ring by ``serving.embed_caches``, and
    its pending token is that prefill's greedy token."""
    eng = _admit_engine(arch, None)
    cfg, dp = eng.cfg, eng.dparams
    eng.submit(Request(_prompts(cfg, (6,), seed=6), max_tokens=4))
    eng.step()
    toks = _prompts(cfg, (5,), seed=5)
    eng.submit(Request(toks, max_tokens=4))
    eng.step()
    row = np.zeros((1, ADMIT["prefill_len"]), np.int32)
    row[0, :5] = toks
    logits, pf = serving_jits(cfg, "jnp")["prefill"](
        dp, {"tokens": jnp.asarray(row)}, jnp.asarray([5], jnp.int32))
    want = serving.embed_caches(
        pf, serving.init_caches(cfg, 1, ADMIT["max_len"]))
    for w, got in zip(_leaves(want), _leaves(eng.caches)):
        np.testing.assert_array_equal(got[:, 1], w[:, 0])
    assert int(np.asarray(eng.tokens)[1, 0]) == int(
        np.argmax(np.asarray(logits)[0, -1]))


@pytest.mark.parametrize("arch,page_size", ADMIT_FAMILIES)
def test_admission_program_takes_one_row(arch, page_size):
    """The admission program is lowered at a (1, prefill_len) batch."""
    eng = _admit_engine(arch, page_size)
    args, _ = eng.lower_admit().args_info
    assert args[1]["tokens"].shape == (1, ADMIT["prefill_len"])
    assert args[2].shape == (1,)                    # its length


def test_engine_construction_reuses_module_jits():
    """Satellite: serving executables are module-cached — constructing a
    second engine (or calling serving_jits twice) must reuse the same
    compiled wrappers, never rebuild them per instance."""
    cfg, dp = _setup("qwen1.5-4b")
    assert serving_jits(cfg, "jnp")["prefill"] \
        is serving_jits(cfg, "jnp")["prefill"]
    mk = lambda: ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                               max_len=24, prefill_len=8)
    e1, e2 = mk(), mk()
    assert e1._admit_fn is e2._admit_fn and e1._step_fn is e2._step_fn


def test_serving_session_is_removed():
    """Satellite: the deprecated lockstep ServingSession (PR 5) is gone —
    request-level serving goes through ServingEngine, lockstep baselines
    through serving_jits loops."""
    assert not hasattr(engine_mod, "ServingSession")


# ---------------------------------------------------------------------------
# Per-slot decode mechanics (serving-level)
# ---------------------------------------------------------------------------

def test_scalar_pos_broadcasts_to_vector():
    cfg, dp = _setup("qwen1.5-4b")
    tok = jnp.ones((2, 1), jnp.int32)
    lg_s, c_s = serving.decode_step(dp, cfg, tok,
                                    serving.init_caches(cfg, 2, 16),
                                    jnp.asarray(4, jnp.int32))
    lg_v, c_v = serving.decode_step(dp, cfg, tok,
                                    serving.init_caches(cfg, 2, 16),
                                    jnp.full((2,), 4, jnp.int32))
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
    for a, b in zip(jax.tree_util.tree_leaves(c_s),
                    jax.tree_util.tree_leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v3-671b",
                                  "mamba2-780m"])
def test_dead_slots_leave_caches_untouched(arch):
    """live=False rows must drop every cache write: attention/MLA ring
    scatters and SSM state updates alike."""
    cfg, dp = _setup(arch)
    caches = serving.init_caches(cfg, 2, 16)
    # populate both rows, then step again with row 1 dead
    _, c1 = serving.decode_step(dp, cfg, jnp.ones((2, 1), jnp.int32), caches,
                                jnp.full((2,), 3, jnp.int32))
    _, c2 = serving.decode_step(dp, cfg, jnp.full((2, 1), 5, jnp.int32), c1,
                                jnp.full((2,), 4, jnp.int32),
                                live=jnp.asarray([True, False]))
    changed = dead_same = True
    for a, b in zip(jax.tree_util.tree_leaves(c1),
                    jax.tree_util.tree_leaves(c2)):
        a, b = np.asarray(a), np.asarray(b)
        dead_same &= np.array_equal(a[:, 1], b[:, 1])
        changed &= not np.array_equal(a[:, 0], b[:, 0])
    assert dead_same, "dead slot's cache was written"
    assert changed, "live slot's cache did not advance"


def test_ragged_positions_decode_each_row_at_its_own_depth():
    """Two slots at different positions attend to different history depths:
    zeroing cache entries above a row's pos must not change that row."""
    cfg, dp = _setup("qwen1.5-4b")
    caches = serving.init_caches(cfg, 2, 16)
    pos = jnp.asarray([2, 7], jnp.int32)
    tok = jnp.ones((2, 1), jnp.int32)
    lg, _ = serving.decode_step(dp, cfg, tok, caches, pos)
    # wipe ring entries 8.. (above both rows): logits must be unchanged
    wiped = jax.tree_util.tree_map(
        lambda t: t.at[:, :, :, 8:].set(0) if t.ndim == 5 and t.shape[3] == 16
        else t, caches)
    lg2, _ = serving.decode_step(dp, cfg, tok, wiped, pos)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg2))


# ---------------------------------------------------------------------------
# Sampling helper (satellite)
# ---------------------------------------------------------------------------

def test_sampling_greedy_is_argmax():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((3, 1, 17)))
    np.testing.assert_array_equal(np.asarray(sample(logits, GREEDY)),
                                  np.argmax(np.asarray(logits), axis=-1))


def test_sampling_top1_equals_greedy_for_any_key():
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((4, 9)))
    p = SamplingParams(kind="top_k", top_k=1, temperature=0.7)
    for seed in range(3):
        np.testing.assert_array_equal(
            np.asarray(sample(logits, p, jax.random.PRNGKey(seed))),
            np.argmax(np.asarray(logits), axis=-1))


def test_sampling_top_k_restricts_support():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((64, 11)))
    p = SamplingParams(kind="top_k", top_k=3)
    ids = np.asarray(sample(logits, p, jax.random.PRNGKey(0)))
    top3 = np.argsort(np.asarray(logits), axis=-1)[:, -3:]
    assert all(ids[i] in top3[i] for i in range(ids.shape[0]))


def test_sampling_top_k_clamps_to_vocab():
    """Regression: ``top_k > vocab_size`` used to crash inside
    ``jax.lax.top_k``; it must mean "no restriction" instead, and the
    clamped kind must stay usable under jit (the serving steps jit it)."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((8, 11)))
    big = SamplingParams(kind="top_k", top_k=999, temperature=0.8)
    full = SamplingParams(kind="top_k", top_k=11, temperature=0.8)
    key = jax.random.PRNGKey(3)
    a = np.asarray(sample(logits, big, key))            # must not raise
    np.testing.assert_array_equal(a, np.asarray(sample(logits, full, key)))
    jitted = jax.jit(lambda lg, k: sample(lg, big, k))
    np.testing.assert_array_equal(np.asarray(jitted(logits, key)), a)


def test_sampling_top_k_keeps_kth_ties():
    """Tie pinning: every logit EQUAL to the kth-largest stays in the
    support (the strict ``lg < kth`` mask) — top_k=1 over an all-tied row
    can therefore sample any index."""
    logits = jnp.zeros((256, 5))
    p = SamplingParams(kind="top_k", top_k=1)
    ids = np.asarray(sample(logits, p, jax.random.PRNGKey(0)))
    assert len(np.unique(ids)) > 1                     # ties all reachable


def test_sampling_temperature_deterministic_per_key():
    logits = jnp.asarray(np.random.default_rng(3).standard_normal((5, 13)))
    p = SamplingParams(kind="temperature", temperature=1.3)
    a = sample(logits, p, jax.random.PRNGKey(7))
    b = sample(logits, p, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).max() < 13 and np.asarray(a).min() >= 0


def test_sampling_validation():
    with pytest.raises(ValueError):
        SamplingParams(kind="nucleus")
    with pytest.raises(ValueError):
        SamplingParams(kind="top_k", top_k=0)
    with pytest.raises(ValueError):
        sample(jnp.zeros((2, 4)), SamplingParams(kind="temperature"))


def test_lockstep_generate_with_sampling_params():
    """The lockstep oracle consumes the shared helper too (satellite):
    stochastic generation is deterministic per key and shaped like
    greedy."""
    cfg, dp = _setup("qwen1.5-4b")
    batch = {"tokens": jnp.asarray(_prompts(cfg, (2, 8), seed=9))}
    p = SamplingParams(kind="top_k", top_k=4, temperature=0.9)
    t1 = _lockstep_generate(cfg, dp, batch, gen=3, max_len=12,
                            key=jax.random.PRNGKey(0), sampling=p)
    t2 = _lockstep_generate(cfg, dp, batch, gen=3, max_len=12,
                            key=jax.random.PRNGKey(0), sampling=p)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (2, 4)


# ---------------------------------------------------------------------------
# Submit validation
# ---------------------------------------------------------------------------

def test_submit_rejects_overflow():
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2, max_len=16,
                        prefill_len=8)
    with pytest.raises(ValueError, match="prompt length"):
        eng.submit(Request(np.zeros(9, np.int32)))
    with pytest.raises(ValueError, match="overflows"):
        eng.submit(Request(np.zeros(8, np.int32), max_tokens=10))
    rid = eng.submit(Request(np.zeros(8, np.int32), max_tokens=9))
    assert isinstance(rid, int)
