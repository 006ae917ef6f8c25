"""Paged KV cache + radix prefix sharing (repro.cache, PR 6).

Three layers of guards:

* **host bookkeeping** — allocator refcount/free-list invariants and the
  radix longest-prefix contract, driven with randomized interleavings
  against shadow models;
* **device helpers** — gather/scatter page arithmetic reconstructs the
  dense per-slot ring bit-exactly (the NULL page reads as zeros, drop
  sentinels never write);
* **serving level** — the paged ``ServingEngine`` is token-for-token
  bit-identical to the dense engine on the staggered traces of
  tests/test_continuous_batching.py (jnp AND pallas), never recompiles
  after warmup, admits fully-cached prompts with zero prefill launches,
  evicts under pool pressure, and keeps strictly fewer KV bytes resident
  than the dense rings.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api.scheduler import Request, ServingEngine, auto_page_size
from repro.cache import (DoubleFree, NULL_PAGE, PageAllocator, PageError,
                         PagesExhausted, PagePool, RadixIndex, gather_pages,
                         scatter_prefill, write_coords)
from repro.api.engine import serving_jits
from repro.models import serving
from test_continuous_batching import (ADMIT, STAGGER, _admit_engine,
                                      _fill_two_slots, _leaves, _setup,
                                      _stagger_trace)


# ---------------------------------------------------------------------------
# PageAllocator: refcount + free-list invariants
# ---------------------------------------------------------------------------

def test_allocator_lifecycle():
    al = PageAllocator(5)                       # page 0 reserved (NULL)
    assert al.num_allocatable == 4 and al.free_count == 4 and al.in_use == 0
    pages = [al.alloc() for _ in range(4)]
    assert sorted(pages) == [1, 2, 3, 4]        # NULL is never handed out
    assert al.free_count == 0 and al.in_use == 4
    al.retain(pages[0])                         # a sharer maps it too
    assert al.release(pages[0]) == 1            # still referenced
    assert al.release(pages[0]) == 0            # last reference gone
    al.free(pages[0])
    assert al.free_count == 1 and al.is_free(pages[0])
    assert al.alloc() == pages[0]               # recycled


def test_allocator_guards():
    al = PageAllocator(3)
    with pytest.raises(PagesExhausted):
        for _ in range(3):
            al.alloc()
    p = 1
    assert al.refcount[p] == 1
    with pytest.raises(PageError):
        al.free(p)                              # still referenced
    al.release(p)
    with pytest.raises(DoubleFree):
        al.release(p)                           # below zero
    with pytest.raises(PageError):
        al.retain(p)                            # unreferenced
    al.free(p)
    with pytest.raises(DoubleFree):
        al.free(p)                              # already free
    with pytest.raises(PageError):
        al.revive(p)                            # free, not resident
    with pytest.raises(PageError):
        al.retain(NULL_PAGE)                    # reserved id
    with pytest.raises(ValueError):
        PageAllocator(1)                        # nothing allocatable


def test_allocator_randomized_shadow_model():
    """Random alloc/retain/release/free/revive interleavings against a
    plain dict shadow; the guarded transitions must agree with the shadow
    at every step and the count invariant must hold throughout."""
    rng = np.random.default_rng(0)
    al = PageAllocator(9)
    ref = {}                                    # page -> refcount (held pages)
    parked = set()                              # refcount-0, not freed
    for _ in range(2000):
        op = rng.integers(0, 5)
        if op == 0:                             # alloc
            if al.free_count:
                p = al.alloc()
                assert p not in ref and p not in parked
                ref[p] = 1
            else:
                with pytest.raises(PagesExhausted):
                    al.alloc()
        elif op == 1 and ref:                   # retain
            p = int(rng.choice(list(ref)))
            al.retain(p)
            ref[p] += 1
        elif op == 2 and ref:                   # release
            p = int(rng.choice(list(ref)))
            assert al.release(p) == ref[p] - 1
            ref[p] -= 1
            if ref[p] == 0:
                del ref[p]
                parked.add(p)
        elif op == 3 and parked:                # free a parked page
            p = int(rng.choice(list(parked)))
            parked.discard(p)
            al.free(p)
        elif op == 4 and parked:                # revive a parked page
            p = int(rng.choice(list(parked)))
            parked.discard(p)
            al.revive(p)
            ref[p] = 1
        assert al.in_use == len(ref) + len(parked)
        assert al.free_count + al.in_use == al.num_allocatable
        for p, c in ref.items():
            assert al.refcount[p] == c and not al.is_free(p)


# ---------------------------------------------------------------------------
# RadixIndex: longest full-page prefix
# ---------------------------------------------------------------------------

def test_radix_longest_prefix_full_pages_only():
    ix = RadixIndex(4)
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]      # 2 full pages + tail of 2
    assert ix.insert(toks, [7, 8]) == {7, 8}
    assert ix.match(toks) == [7, 8]             # tail never matches
    assert ix.match(toks[:8]) == [7, 8]
    assert ix.match(toks[:7]) == [7]            # second page incomplete
    assert ix.match([1, 2, 3, 4, 0, 0, 0, 0]) == [7]
    assert ix.match([9, 9, 9, 9]) == []
    assert len(ix) == 2 and 7 in ix and 8 not in RadixIndex(4)


def test_radix_first_writer_wins():
    ix = RadixIndex(2)
    assert ix.insert([1, 2, 3, 4], [5, 6]) == {5, 6}
    # duplicate path: existing pages kept, nothing newly indexed
    assert ix.insert([1, 2, 3, 4], [9, 9]) == set()
    assert ix.match([1, 2, 3, 4]) == [5, 6]
    # diverging second page chains a sibling under the shared first node
    assert ix.insert([1, 2, 7, 7], [9, 10]) == {10}
    assert ix.match([1, 2, 7, 7]) == [5, 10]
    with pytest.raises(ValueError):
        ix.insert([8, 8], [10])                 # page already indexed
    with pytest.raises(ValueError):
        ix.insert([8, 8], [11, 12])             # more pages than full keys


def test_radix_evict_lru_leaf_first():
    ix = RadixIndex(1)
    ix.insert([1, 2, 3], [4, 5, 6])             # chain 4 -> 5 -> 6
    with pytest.raises(ValueError):
        ix.remove(4)                            # interior node
    assert ix.evict_lru(lambda p: True) == 6    # leaf first
    ix.insert([9], [7])
    ix.match([1, 2])                            # bump the 4 -> 5 branch
    assert ix.evict_lru(lambda p: True) == 7    # LRU leaf
    assert ix.evict_lru(lambda p: p != 5) is None   # nothing evictable
    assert ix.evict_lru(lambda p: True) == 5


def test_radix_randomized_interleavings_match_shadow():
    """Random inserts/matches over a tiny alphabet (so prefixes collide
    constantly) must agree with a shadow dict keyed on full-page paths."""
    rng = np.random.default_rng(1)
    T = 2
    ix = RadixIndex(T)
    shadow = {}                                 # path tuple -> page
    next_page = 1
    for _ in range(400):
        toks = rng.integers(0, 3, rng.integers(0, 9)).tolist()
        keys = [tuple(toks[i * T:(i + 1) * T]) for i in range(len(toks) // T)]
        if rng.random() < 0.5:                  # insert
            pages = list(range(next_page, next_page + len(keys)))
            got = ix.insert(toks, pages)
            want = set()
            for j, k in enumerate(keys):
                path = tuple(keys[:j + 1])
                if path not in shadow:
                    shadow[path] = pages[j]
                    want.add(pages[j])
            assert got == want
            next_page += len(keys)
        else:                                   # match == shadow walk
            want = []
            for j, k in enumerate(keys):
                page = shadow.get(tuple(keys[:j + 1]))
                if page is None:
                    break
                want.append(page)
            assert ix.match(toks) == want
    assert len(ix) == len(shadow)


# ---------------------------------------------------------------------------
# PagePool: admission / release / eviction lifecycle
# ---------------------------------------------------------------------------

def test_pool_share_release_revive_cycle():
    pool = PagePool(6, page_size=2)             # 5 allocatable
    toks = [1, 2, 3, 4]
    pages = pool.alloc(2)
    pool.index_prompt(toks, pages)
    assert pool.match_prefix(toks + [9]) == pages
    pool.acquire(pages)                         # a second slot shares them
    pool.release(pages)                         # first slot finishes
    assert pool.in_use == 2 and not pool.is_resident(pages[0])
    pool.release(pages)                         # last reference: parked
    assert all(pool.is_resident(p) for p in pages)
    assert pool.available == 5                  # free + resident is exact
    pool.acquire(pool.match_prefix(toks))       # revived copy-free
    assert not pool.is_resident(pages[0])
    pool.release(pages)


def test_pool_unindexed_pages_free_on_release():
    pool = PagePool(4, page_size=2)
    pages = pool.alloc(3)
    pool.release(pages)
    assert pool.allocator.free_count == 3 and pool.in_use == 0


def test_pool_alloc_evicts_cold_resident_pages():
    pool = PagePool(4, page_size=1)             # 3 allocatable
    for toks in ([1], [2], [3]):
        pg = pool.alloc(1)
        pool.index_prompt(toks, pg)
        pool.release(pg)
        pool.match_prefix([1])                  # keep [1] hottest
    assert pool.available == 3 and pool.allocator.free_count == 0
    pool.alloc(2)                               # must evict two cold pages
    assert pool.evictions == 2
    assert pool.match_prefix([1]) != []         # the hot page survived
    with pytest.raises(PagesExhausted):
        pool.alloc(2)                           # 1 resident left, need 2


# ---------------------------------------------------------------------------
# Device helpers: paged gather/scatter == the dense ring
# ---------------------------------------------------------------------------

def test_gather_pages_reconstructs_dense_ring():
    rng = np.random.default_rng(2)
    NP, KV, T, F = 6, 2, 4, 3
    pool = jnp.asarray(rng.standard_normal((NP, KV, T, F)), jnp.float32)
    pool = pool.at[NULL_PAGE].set(0.0)          # the NULL-page convention
    pages = jnp.asarray([[3, 1, NULL_PAGE], [2, 5, 4]], jnp.int32)
    got = np.asarray(gather_pages(pool, pages))
    assert got.shape == (2, KV, 3 * T, F)
    for b in range(2):
        want = np.concatenate([np.asarray(pool[int(p)])
                               for p in pages[b]], axis=1)
        np.testing.assert_array_equal(got[b], want)
    # unmapped tail reads exact zeros — the dense empty-slot convention
    assert not got[0, :, 2 * T:].any()


def test_write_coords_targets_and_drop_sentinels():
    pages = jnp.asarray([[2, 3], [4, NULL_PAGE], [5, 6]], jnp.int32)
    pos = jnp.asarray([5, 6, 9], jnp.int32)     # page 1 off 1 / pg 1 / OOB
    live = jnp.asarray([True, True, True])
    phys, off = write_coords(pos, live, pages, page_size=4, num_pages=7)
    # row 0 writes page 3 offset 1; row 1's page is NULL -> dropped;
    # row 2's position is past the table -> dropped
    np.testing.assert_array_equal(np.asarray(phys), [3, 7, 7])
    np.testing.assert_array_equal(np.asarray(off)[:1], [1])
    phys, _ = write_coords(pos, jnp.asarray([False, True, True]), pages,
                           page_size=4, num_pages=7)
    assert int(phys[0]) == 7                    # dead rows drop too


def test_scatter_prefill_writes_owned_pages_only():
    rng = np.random.default_rng(3)
    X, B, T, F, NP = 2, 2, 2, 3, 5
    pool = jnp.zeros((X, NP, T, F), jnp.float32)
    pf = jnp.asarray(rng.standard_normal((X, B, 2 * T, F)), jnp.float32)
    # slot 0 owns pages (1, 2); slot 1 owns page 3, second entry dropped
    wp = np.asarray([1, 2, 3, NP], np.int32)
    out = np.asarray(scatter_prefill(pool, pf, jnp.asarray(wp)))
    np.testing.assert_array_equal(out[:, 1], np.asarray(pf[:, 0, :T]))
    np.testing.assert_array_equal(out[:, 2], np.asarray(pf[:, 0, T:]))
    np.testing.assert_array_equal(out[:, 3], np.asarray(pf[:, 1, :T]))
    assert not out[:, NULL_PAGE].any() and not out[:, 4].any()


# ---------------------------------------------------------------------------
# Serving level: paged engine == dense engine, bit for bit
# ---------------------------------------------------------------------------

def _run_trace(cfg, dp, backend, page_size, seed, **kw):
    eng = ServingEngine(cfg, dp, backend=backend, max_slots=STAGGER["B"],
                        max_len=STAGGER["M"], prefill_len=STAGGER["P"],
                        page_size=page_size, **kw)
    outs = eng.run(_stagger_trace(cfg, seed), STAGGER["arrivals"])
    return eng, outs


PARITY_CASES = [
    ("qwen1.5-4b", "jnp"),
    ("deepseek-v3-671b", "jnp"),
    ("qwen1.5-4b", "pallas"),
]


@pytest.mark.parametrize("arch,backend", PARITY_CASES)
def test_paged_engine_bit_identical_to_dense(arch, backend):
    """The tentpole contract: page tables change memory layout only.  The
    gather reconstructs each slot's dense ring exactly, so every launch
    sees operand-identical attention inputs and the token streams match
    bit for bit — on the jnp fallback AND through the Pallas kernels."""
    over = ({"capacity_factor": 64.0} if arch == "deepseek-v3-671b" else {})
    cfg, dp = _setup(arch, **over)
    dense_eng, dense = _run_trace(cfg, dp, backend, None, seed=11)
    paged_eng, paged = _run_trace(cfg, dp, backend, "auto", seed=11)
    assert paged_eng.page_size is not None      # really exercised paging
    for i in sorted(dense):
        np.testing.assert_array_equal(paged[i].tokens, dense[i].tokens)
        assert paged[i].finish_reason == dense[i].finish_reason
    # identical schedule, launch for launch
    for k in ("prefill_launches", "decode_launches", "useful_tokens"):
        assert paged_eng.stats[k] == dense_eng.stats[k]


PAGED_FAMILIES = ["qwen1.5-4b", "deepseek-v3-671b", "zamba2-1.2b"]


def _prompt_of(cfg, n):
    return np.random.default_rng(n).integers(
        0, cfg.vocab_size, n).astype(np.int32)


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_paged_admission_leaves_other_slots_untouched(arch):
    """Paged: an admission writes only the pages its slot owns (and, in
    the hybrid family, its slot of the per-slot SSM state); every other
    page and slot, and every other pending token, is bit-identical."""
    eng = _admit_engine(arch, "auto")
    _fill_two_slots(eng)
    before, tok = _leaves(eng.caches), np.asarray(eng.tokens).copy()
    eng.submit(Request(_prompt_of(eng.cfg, 7), max_tokens=4))
    assert eng.step()["kind"] == "prefill"
    own = [int(p) for p in eng._pages[2] if p != NULL_PAGE]
    assert own
    mask = jax.tree_util.tree_leaves(serving.paged_leaf_mask(eng.cfg))
    for paged, a, b in zip(mask, before, _leaves(eng.caches)):
        keep = np.setdiff1d(np.arange(a.shape[1]), own if paged else [2])
        np.testing.assert_array_equal(a[:, keep], b[:, keep])
    np.testing.assert_array_equal(tok[[0, 1, 3]],
                                  np.asarray(eng.tokens)[[0, 1, 3]])


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_paged_admitted_slot_equals_prefill_alone(arch):
    """Paged: the admitted slot's pages, gathered back into its ring, and
    its per-slot leaves hold ``serving.prefill`` of its prompt alone,
    embedded by ``serving.embed_caches``, over the prompt pages it
    owns."""
    eng = _admit_engine(arch, "auto")
    cfg = eng.cfg
    eng.submit(Request(_prompt_of(cfg, 6), max_tokens=4))
    eng.step()
    toks = _prompt_of(cfg, 5)
    eng.submit(Request(toks, max_tokens=4))
    eng.step()
    row = np.zeros((1, ADMIT["prefill_len"]), np.int32)
    row[0, :5] = toks
    _, pf = serving_jits(cfg, "jnp")["prefill"](
        eng.dparams, {"tokens": jnp.asarray(row)}, jnp.asarray([5], jnp.int32))
    want = _leaves(serving.embed_caches(
        pf, serving.init_caches(cfg, 1, ADMIT["max_len"])))
    pages = jnp.asarray(eng._pages[1:2])
    n = -(-5 // eng.page_size) * eng.page_size     # positions it wrote
    mask = jax.tree_util.tree_leaves(serving.paged_leaf_mask(cfg))
    for paged, w, got in zip(mask, want, _leaves(eng.caches)):
        if not paged:
            np.testing.assert_array_equal(got[:, 1], w[:, 0])
            continue
        for x in range(got.shape[0]):
            ring = np.asarray(gather_pages(jnp.asarray(got[x]), pages))
            np.testing.assert_array_equal(ring[0, ..., :n, :],
                                          w[x, 0, ..., :n, :])


def test_paged_zero_recompiles_after_warmup():
    cfg, dp = _setup("qwen1.5-4b")
    eng, _ = _run_trace(cfg, dp, "jnp", "auto", seed=12)
    warm = eng.compile_counts()
    assert warm == {"admit": 1, "step": 1}
    eng2, _ = _run_trace(cfg, dp, "jnp", "auto", seed=13)
    assert eng2.stats["prefill_launches"] >= 2  # slots really were refilled
    assert eng2.compile_counts() == warm, \
        "paged serving recompiled after warmup"


def test_full_prefix_hit_admits_with_zero_prefill_launches():
    """A prompt whose full-page prefix is entirely cached admits copy-free:
    no prefill launch, pages mapped by refcount bump, and the generated
    stream matches the uncached run of the same request."""
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2, max_len=24,
                        prefill_len=8)          # auto page_size 8
    toks = np.random.default_rng(14).integers(
        0, cfg.vocab_size, 8).astype(np.int32)
    first = eng.run([Request(toks, max_tokens=5)])
    pre = eng.stats["prefill_launches"]
    again = eng.run([Request(toks, max_tokens=5)])
    assert eng.stats["prefill_launches"] == pre             # zero prefills
    assert eng.stats["zero_prefill_admits"] == 1
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["cached_tokens"] == 8
    np.testing.assert_array_equal(again[0].tokens, first[0].tokens)


def test_partial_prefix_hit_matches_unshared_engine():
    """Sharing only the first page of a longer prompt must not change a
    token: shared pages hold bit-identical KV to what the request's own
    prefill would have written (row-independent prefill, same weights)."""
    cfg, dp = _setup("qwen1.5-4b")
    rng = np.random.default_rng(15)
    a = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    b = np.concatenate([a[:8],
                        rng.integers(0, cfg.vocab_size, 8).astype(np.int32)])
    mk = lambda share: ServingEngine(cfg, dp, backend="jnp", max_slots=2,
                                     max_len=24, prefill_len=16,
                                     prefix_sharing=share)
    eng = mk(True)
    assert eng.page_size == 8                   # gcd(24, 16): b shares page 0
    eng.run([Request(a, max_tokens=3)])
    base_hits = eng.stats["prefix_hits"]
    shared = eng.run([Request(b, max_tokens=6)])
    assert eng.stats["prefix_hits"] == base_hits + 1
    assert eng.stats["cached_tokens"] >= eng.page_size
    ref = mk(False).run([Request(b, max_tokens=6)])
    np.testing.assert_array_equal(shared[0].tokens, ref[0].tokens)


def test_eviction_under_pool_pressure():
    """With a pool too small to keep every finished prompt resident, cold
    prefix pages are evicted LRU-first and serving still completes; the
    free+resident accounting returns to capacity when all slots drain."""
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=1, max_len=24,
                        prefill_len=8, num_pages=4)         # 3 allocatable
    rng = np.random.default_rng(16)
    for _ in range(4):
        toks = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        outs = eng.run([Request(toks, max_tokens=9)])
        assert outs[0].finish_reason == "length"
    assert eng.stats["evictions"] >= 1
    assert eng.pool.available == eng.pool.capacity          # all reclaimed
    assert eng.stats["pages_peak"] <= eng.pool.capacity


def test_deferred_admission_preserves_outputs():
    """When the pool cannot reserve worst-case pages for both requests at
    once, the second is deferred (not dropped) and both token streams still
    match the roomy dense engine."""
    cfg, dp = _setup("qwen1.5-4b")
    reqs = lambda: [Request(np.full(8, 3 + i, np.int32), max_tokens=9)
                    for i in range(2)]
    dense = ServingEngine(cfg, dp, backend="jnp", max_slots=2, max_len=24,
                          prefill_len=8, page_size=None).run(reqs())
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=2, max_len=24,
                        prefill_len=8, num_pages=4)         # one at a time
    outs = eng.run(reqs())
    assert eng.stats["deferred_admissions"] >= 1
    for i in sorted(dense):
        np.testing.assert_array_equal(outs[i].tokens, dense[i].tokens)


def test_kv_bytes_resident_below_dense():
    cfg, dp = _setup("qwen1.5-4b")
    eng, _ = _run_trace(cfg, dp, "jnp", "auto", seed=17)
    assert eng.kv_bytes_resident() < eng.kv_bytes_dense()
    dense_eng, _ = _run_trace(cfg, dp, "jnp", None, seed=17)
    assert dense_eng.kv_bytes_resident() == dense_eng.kv_bytes_dense()


def test_paged_mode_validation():
    cfg, dp = _setup("qwen1.5-4b")
    scfg, sdp = _setup("mamba2-780m")
    # ssm has no ring axis: auto falls back to dense, explicit raises
    assert auto_page_size(scfg, 24, 8) is None
    eng = ServingEngine(scfg, sdp, backend="jnp", max_slots=2, max_len=24,
                        prefill_len=8)
    assert eng.pool is None
    with pytest.raises(ValueError, match="no ring axis"):
        ServingEngine(scfg, sdp, max_slots=2, max_len=24, prefill_len=8,
                      page_size=4)
    with pytest.raises(ValueError, match="paged cache"):
        ServingEngine(cfg, dp, max_slots=2, max_len=24, prefill_len=8,
                      page_size=None, prefix_sharing=True)
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(cfg, dp, max_slots=2, max_len=24, prefill_len=8,
                      page_size=5)


def test_submit_overflow_names_request_and_page_budget():
    """Satellite: the overflow error says which request and what the page
    budget actually is."""
    cfg, dp = _setup("qwen1.5-4b")
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=1, max_len=24,
                        prefill_len=8, num_pages=3)         # capacity 2
    with pytest.raises(ValueError, match=r"request 0:.*needs 3 pages of 8 "
                                         r"tokens, pages free 2/2"):
        eng.submit(Request(np.zeros(8, np.int32), max_tokens=17))
    rid = eng.submit(Request(np.zeros(8, np.int32), max_tokens=9))
    assert rid == 0                             # rejected submit burns no id
