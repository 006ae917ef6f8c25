"""Request-level serving: continuous batching over a paged KV cache.

The pre-PR5 public serving surface was ``ServingSession.generate`` — a
lockstep loop where one fixed batch prefills together, decodes together and
finishes together, so real traffic (requests arriving at different times
with different prompt/output lengths) leaves the fused deployed kernels
idle behind the shortest-job barrier.  :class:`ServingEngine` redesigns the
surface around **requests**:

* ``submit`` queues a :class:`Request`; admission prefills each admitted
  prompt in its own fixed ``(1, prefill_len)`` launch (right-padded, its
  true length via ``serving.prefill(..., lens=...)``) and writes only that
  request's slot — in-flight slots are neither read nor written, so
  prefill of new arrivals interleaves with decode of in-flight ones, and
  the chip computes one prompt's rows per admitted request, not
  ``max_slots`` of them;
* every decode tick is ONE fixed-width ``decode_step`` launch with a
  **per-slot position vector** and a live mask (freed slots drop their ring
  writes / SSM state updates — models/attention.py, models/ssm.py);
* a finished slot (EOS or ``max_tokens``) is reclaimed and refilled from
  the admission queue **without re-jitting**: every launch has the same
  static shapes, so after one warmup pass the jit caches never grow
  (``compile_counts`` exposes the counters the tests and the
  ``continuous_batching`` / ``paged_cache`` benchmark sections assert on).

Paged KV cache (PR 6).  By default the ring leaves are no longer dense
``(max_slots, max_len)`` rows but **physical pages** managed by
``repro.cache``: each slot carries a ``(pages_per_slot,)`` page-table row,
admission allocates only ``ceil(prompt_len / page_size)`` pages and decode
lazily maps one more page each time a slot's position crosses a page
boundary, so resident KV bytes track the tokens actually held instead of
``max_slots * max_len``.  Admission is gated by a **page reservation**
invariant (``available >= reserved``) that guarantees a lazy decode
allocation can never fail mid-request; when the head of the queue does not
fit it waits (strict FIFO, ``deferred_admissions`` stat) while decode keeps
ticking.  A radix index over prompt tokens additionally shares identical
prompt prefixes **copy-free** (``prefix_sharing``, default on for the
``dense`` family): matched full pages are mapped read-only with a refcount
bump, a fully-cached prompt skips its prefill launch entirely (the slot
bootstraps from the last prompt token in its first decode tick — zero
prefill FLOPs), and pages of finished requests stay cached while free
space lasts (LRU leaf-first eviction under pressure).  ``page_size=None``
restores the dense PR5 pool bit-for-bit — the parity oracle the paged
tests compare against.

Numerical contract: the paged engine's launches gather per-slot ring views
that are element-for-element the dense rings (``repro.cache.paged``), so
its tokens are **bit-identical** to the dense engine's on any trace without
prefix hits; a full-prefix hit samples its first token from a decode-step
launch instead of the prefill launch (same math, different launch path).
MoE couples rows through expert-capacity overflow, so sharing pages built
under a different batch composition is approximate — prefix sharing there
is an explicit opt-in.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import sampling as smp
from repro.cache import NULL_PAGE, PagePool
from repro.dist import fault
from repro.dist import sharding as shd


@dataclasses.dataclass
class Request:
    """One generation request.

    ``tokens``: (L,) int prompt ids; ``max_tokens``: total generated tokens
    INCLUDING the one sampled from the prefill logits (so ``max_tokens=G``
    corresponds to the old lockstep ``generate(gen=G-1)``); ``eos_id``:
    stop early when this id is sampled (still counted in the output);
    ``extras``: per-request prefill arrays keyed like the batch dict
    (``frames`` for audio, ``prefix_embeds`` for vlm) — the admission
    launch takes them as its one ``(1, ...)`` row.
    """
    tokens: np.ndarray
    max_tokens: int = 16
    eos_id: Optional[int] = None
    extras: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RequestOutput:
    """A finished request.  The four times are ``time.perf_counter()``
    readings: ``submit`` returned, the request left the admission queue
    for a slot, its first token was read back, it finished."""
    rid: int
    tokens: np.ndarray              # (n_generated,) int32, eos included
    prompt_len: int
    finish_reason: str              # "length" | "eos"
    submitted_s: float
    admitted_s: float
    first_token_s: float
    finished_s: float


# -- tracing -----------------------------------------------------------------
# Every span is a ``jax.profiler.TraceAnnotation``: it lands on the host
# plane of a running profiler session (beside the device ops, on one clock)
# and is a no-op otherwise.  Spans that time a phase also add their
# duration to a cumulative counter in ``ServingEngine.stats``, so the
# counters exist whether or not anything is profiling.
_clock = time.perf_counter
_PHASES = ("plan", "launch", "sync", "record")
_TICK_KINDS = ("admit", "decode", "speculative")


class _Span:
    """One profiler span that adds its wall time to ``stats[key]``.

    ``close`` may end the span before its ``with`` block does, naming the
    counter and the attributes learnt meanwhile (a step's plan phase learns
    its tick kind last); the block's exit is then a no-op.
    """
    __slots__ = ("_stats", "_key", "_ann", "_t0")

    def __init__(self, stats: dict, name: str, key: Optional[str] = None,
                 **attrs):
        self._stats, self._key = stats, key
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = _clock()
        return self

    def close(self, key: Optional[str] = None, **attrs) -> None:
        if self._ann is None:
            return
        dt = _clock() - self._t0
        if attrs:
            self._ann.set_metadata(**attrs)
        self._ann.__exit__(None, None, None)
        self._ann = None
        key = key or self._key
        if key is not None:
            self._stats[key] = self._stats.get(key, 0.0) + dt

    def __exit__(self, *exc):
        self.close()


class _GCPauses:
    """Pauses of Python's cyclic garbage collector, process-wide: one
    ``gc.callbacks`` hook opens a ``gc`` span (attribute ``generation``)
    when a collection starts and, when it stops, closes it and adds to the
    totals that every engine reads."""
    pause_s = 0.0
    collections = 0
    _open: list = []

    @classmethod
    def hook(cls, phase: str, info: dict) -> None:
        if phase == "start":
            ann = jax.profiler.TraceAnnotation(
                "gc", generation=info["generation"])
            ann.__enter__()
            cls._open.append((ann, _clock()))
        elif cls._open:
            ann, t0 = cls._open.pop()
            cls.pause_s += _clock() - t0
            cls.collections += 1
            ann.__exit__(None, None, None)


# installed once per process: a module reload replaces the hook, engines
# never add one
gc.callbacks[:] = [cb for cb in gc.callbacks
                   if getattr(cb, "__qualname__", None) != "_GCPauses.hook"
                   or getattr(cb, "__module__", None) != __name__]
gc.callbacks.append(_GCPauses.hook)


# Module-level jitted admission/step executables, keyed on (cfg id, backend,
# sampling, page_size, kv_bits): the same hoisting rule as
# engine.serving_jits — two engines over one deployed config share
# executables, and re-constructing an engine never recompiles.  cfg is
# strongly referenced so its id() stays unique.
_ENGINE_JITS: dict = {}


def _engine_jits(cfg, backend: str, sampling: smp.SamplingParams,
                 page_size: Optional[int], kv_bits=None,
                 speculate_k: int = 0, draft_kv_bits=None, mesh=None) -> dict:
    key = (id(cfg), backend, sampling, page_size, kv_bits, speculate_k,
           draft_kv_bits, mesh)
    ent = _ENGINE_JITS.get(key)
    if ent is None:
        from repro.models import serving

        ctx = shd.MeshContext(mesh)

        def _meshed(fn, cache_outs=()):
            """Trace ``fn`` inside the serving-mesh context (fused kernels
            route to their shard_map TP/EP forms, attention/router
            annotations activate) and pin output shardings: the cache trees
            at positions ``cache_outs`` keep their slot/page-axis ``data``
            sharding, every other output (tokens, logits rows, accept
            counts) replicates.  Identity without a mesh — the single-device
            trace is byte-for-byte the pre-mesh one."""
            if not ctx.is_active:
                return fn

            def wrapped(*args):
                with shd.serving_mesh(ctx):
                    out = fn(*args)
                    return tuple(
                        ctx.constrain_caches(o) if i in cache_outs
                        else ctx.constrain_replicated(o)
                        for i, o in enumerate(out))
            return wrapped

        def _put_token(tok_old, tok, slot):
            """The (max_slots, 1) next-token batch with row ``slot`` set to
            the admitted request's first token."""
            return jax.lax.dynamic_update_slice_in_dim(
                tok_old, tok.astype(tok_old.dtype), slot, axis=0)

        if page_size is None:
            def _admit(dp, batch, lens, slot, tok_old, caches, key):
                """One request's admission: a ``(1, prefill_len)`` prefill
                written into slot ``slot`` (a traced scalar, so one program
                serves every slot).  Every other slot's cache and token are
                neither read nor written.  Returns the next-token batch
                with the admitted row sampled from its last-prompt-token
                logits, and the caches."""
                logits, pf = serving.prefill(dp, cfg, batch, backend,
                                             lens=lens, kv_bits=kv_bits)
                caches = serving.write_slot(pf, caches, slot)
                tok = smp.sample(logits, sampling, key)          # (1, 1)
                return _put_token(tok_old, tok, slot), caches

            def _logits(dp, tokens, caches, pos, live):
                """One decode tick: per-slot positions, live-masked cache."""
                return serving.decode_step(dp, cfg, tokens, caches, pos,
                                           backend, live=live,
                                           kv_bits=kv_bits)
        else:
            def _admit(dp, batch, lens, slot, tok_old, caches, wp_flat,
                       key):
                """Paged admission of one request: a ``(1, prefill_len)``
                prefill whose prompt pages scatter through ``wp_flat
                (n_prompt_pages,)`` (out-of-bounds = skip the write: junk
                tails, prefix-shared read-only pages); per-slot leaves
                (hybrid SSM state, audio cross) are written at ``slot``.
                One launch shape for every slot and prompt."""
                logits, pf = serving.prefill(dp, cfg, batch, backend,
                                             lens=lens, kv_bits=kv_bits)
                caches = serving.merge_paged_caches(cfg, pf, caches, slot,
                                                    wp_flat)
                tok = smp.sample(logits, sampling, key)          # (1, 1)
                return _put_token(tok_old, tok, slot), caches

            def _logits(dp, tokens, caches, pos, live_write, pages):
                """One paged decode tick: the ``(B, pages_per_slot)`` page
                table routes every ring read/write; ``live_write`` also
                masks rows whose write is suppressed for one tick (a
                full-prefix hit whose last prompt position is already
                cached in a shared page)."""
                return serving.decode_step(
                    dp, cfg, tokens, caches, pos, backend, live=live_write,
                    pages=pages, page_size=page_size, kv_bits=kv_bits)

        def _step(*args):
            """The decode tick sampled: ``(*_logits args, key) ->
            (next tokens, caches)``."""
            logits, caches = _logits(*args[:-1])
            return smp.sample(logits, sampling, args[-1]), caches

        ent = {"cfg": cfg,
               "admit": jax.jit(_meshed(_admit, cache_outs=(1,)),
                                donate_argnums=(5,)),
               "step": jax.jit(_meshed(_step, cache_outs=(1,)),
                               donate_argnums=(2,)),
               "logits": jax.jit(_meshed(_logits, cache_outs=(1,)))}

        if speculate_k:
            # Speculative serving replaces the admission executable with a
            # combined verifier+draft prefill of the same one row (the draft
            # pool has no prefix sharing, so it prefills even a request the
            # radix index admits with zero verifier FLOPs: the verifier's
            # page writes then all carry the out-of-bounds sentinel) and
            # adds the round executables: a single-token draft step that
            # also returns the logits row its token was sampled from, and a
            # (k+1)-wide verify that fuses the multi-token decode with
            # rejection sampling (api/sampling.speculative_accept).  The
            # baseline ``step`` stays — it is the suppressed-slot fallback
            # tick.
            if page_size is None:
                def _admit_spec(dp, ddp, batch, lens, slot, tok_old, caches,
                                dcaches, key):
                    logits, pf = serving.prefill(dp, cfg, batch, backend,
                                                 lens=lens, kv_bits=kv_bits)
                    caches = serving.write_slot(pf, caches, slot)
                    _, dpf = serving.prefill(ddp, cfg, batch, backend,
                                             lens=lens, kv_bits=draft_kv_bits)
                    dcaches = serving.write_slot(dpf, dcaches, slot)
                    tok = smp.sample(logits, sampling, key)
                    return _put_token(tok_old, tok, slot), caches, dcaches

                def _draft(ddp, tokens, dcaches, pos, live, key):
                    lg, dcaches = serving.decode_step(
                        ddp, cfg, tokens, dcaches, pos, backend, live=live,
                        kv_bits=draft_kv_bits)
                    return smp.sample(lg, sampling, key), lg[:, 0], dcaches

                def _verify(dp, tokens, caches, pos, live, dtok, dlg, key):
                    lg, caches = serving.decode_step(
                        dp, cfg, tokens, caches, pos, backend, live=live,
                        kv_bits=kv_bits)
                    acc, out = smp.speculative_accept(dtok, dlg, lg,
                                                      sampling, key)
                    return acc, out, caches
            else:
                def _admit_spec(dp, ddp, batch, lens, slot, tok_old, caches,
                                dcaches, wp_flat, dwp_flat, key):
                    logits, pf = serving.prefill(dp, cfg, batch, backend,
                                                 lens=lens, kv_bits=kv_bits)
                    caches = serving.merge_paged_caches(cfg, pf, caches,
                                                        slot, wp_flat)
                    _, dpf = serving.prefill(ddp, cfg, batch, backend,
                                             lens=lens, kv_bits=draft_kv_bits)
                    dcaches = serving.merge_paged_caches(cfg, dpf, dcaches,
                                                         slot, dwp_flat)
                    tok = smp.sample(logits, sampling, key)
                    return _put_token(tok_old, tok, slot), caches, dcaches

                def _draft(ddp, tokens, dcaches, pos, live, pages, key):
                    lg, dcaches = serving.decode_step(
                        ddp, cfg, tokens, dcaches, pos, backend, live=live,
                        pages=pages, page_size=page_size,
                        kv_bits=draft_kv_bits)
                    return smp.sample(lg, sampling, key), lg[:, 0], dcaches

                def _verify(dp, tokens, caches, pos, live, pages, dtok, dlg,
                            key):
                    lg, caches = serving.decode_step(
                        dp, cfg, tokens, caches, pos, backend, live=live,
                        pages=pages, page_size=page_size, kv_bits=kv_bits)
                    acc, out = smp.speculative_accept(dtok, dlg, lg,
                                                      sampling, key)
                    return acc, out, caches

            ent["admit"] = jax.jit(_meshed(_admit_spec, cache_outs=(1, 2)),
                                   donate_argnums=(6, 7))
            ent["draft_step"] = jax.jit(_meshed(_draft, cache_outs=(2,)),
                                        donate_argnums=(2,))
            ent["verify"] = jax.jit(_meshed(_verify, cache_outs=(2,)),
                                    donate_argnums=(2,))
        _ENGINE_JITS[key] = ent
    return ent


def auto_page_size(cfg, max_len: int, prefill_len: int,
                   cap: int = 16) -> Optional[int]:
    """Default page size: the largest divisor of gcd(max_len, prefill_len)
    not exceeding ``cap`` (both widths must split into whole pages so the
    gathered ring and the scattered prefill stay exact-shape).  ``None``
    (dense) for families with no ring to page (ssm)."""
    from repro.models import serving
    if not serving.supports_paging(cfg):
        return None
    g = math.gcd(max_len, prefill_len)
    return max(t for t in range(1, min(cap, g) + 1) if g % t == 0)


class _Slot:
    __slots__ = ("rid", "prompt_len", "max_tokens", "eos_id", "generated",
                 "worst", "mapped", "admitted_s", "first_token_s")

    def __init__(self, rid, prompt_len, max_tokens, eos_id, admitted_s,
                 worst=0, mapped=0):
        self.rid, self.prompt_len = rid, prompt_len
        self.max_tokens, self.eos_id = max_tokens, eos_id
        self.generated: List[int] = []
        self.worst = worst              # page budget ceil((L+mt-1)/T)
        self.mapped = mapped            # pages currently in the table row
        self.admitted_s = admitted_s
        self.first_token_s = 0.0


class ServingEngine:
    """Continuous-batching serving engine over a deployed LM.

        eng = ServingEngine(cfg, dparams, backend="jnp",
                            max_slots=4, max_len=64, prefill_len=16)
        rid = eng.submit(Request(prompt_ids, max_tokens=20))
        while eng.step()["kind"] != "idle": ...
        outs = eng.collect()                 # finished RequestOutputs

    or, for a whole trace, ``eng.run(requests, arrivals)``.  One engine
    ``step()`` is an admission tick when slots and pages are free and
    requests are queued (one ``(1, prefill_len)`` prefill launch per
    admitted request), else a decode tick over the live slots (one
    launch); either ends in one host sync.

    ``page_size``: ``"auto"`` (default) pages the KV cache with
    :func:`auto_page_size`; an int forces that page size; ``None`` serves
    the dense PR5 slot pool.  ``num_pages`` (paged mode) sizes the physical
    pool — default ``1 + max_slots * max_len / page_size``, the dense
    capacity plus the NULL page, so default engines never defer.
    ``prefix_sharing``: ``"auto"`` enables the radix prompt index for the
    ``dense`` family; ``True`` additionally allows ``moe`` (approximate —
    expert-capacity coupling makes prefill rows batch-dependent); families
    whose generation depends on non-token inputs (vlm prefix embeds, audio
    frames) or uncached recurrent state (ssm, hybrid) reject it.

    ``kv_bits``: cache quantization policy (``serving.kv_specs``).  ``None``
    (default) keeps the legacy int8-per-token cache; an int or bit-tuple
    stores the rings channel-wise packed (models/kv_quant.py) — page pools
    shrink to the packed bytes, ``kv_bytes_*`` price the packed layout, and
    ``backend="pallas"`` decodes GQA rings through the fused dequant
    decode-attention kernel.  Part of the jit key: one policy = one warmup,
    zero recompiles after.

    ``speculate_k`` > 0 turns every decode tick into a speculative round
    (``_speculative_tick``): a draft model (``draft_dparams``, default the
    verifier itself; pair it with a low-bit re-quantization from
    ``serving.draft_model`` / dual-policy ``Engine.deploy``) proposes k
    tokens in k single-token launches against its own private KV pool
    (``draft_kv_bits`` independently settable), then ONE (k+1)-wide verify
    launch scores all of them and rejection sampling keeps the longest
    valid prefix plus a correction token.  Under greedy sampling the
    emitted stream is bit-identical to the non-speculative engine's on the
    same backend — the parity anchor tests/test_speculative.py pins.

    Tracing: every ``step()`` is four consecutive phase spans, ``engine.
    plan`` (scheduling, page plan, argument transfers), ``engine.launch``
    (the jitted call), ``engine.sync`` (reading the sampled tokens back)
    and ``engine.record`` (tokens to requests, finishing, page release),
    each with attributes ``tick``, ``kind``, ``live`` and ``admitted``;
    each adds its wall time to ``stats["{kind}_{phase}_s"]`` for kind
    ``admit``, ``decode`` or ``speculative``.  ``submit`` and each finished
    request are spans ``engine.submit`` and ``engine.finish``; garbage
    collections are ``gc`` spans, their totals in ``stats["gc_pause_s"]``
    and ``stats["gc_collections"]``.  docs/serving.md lists every counter.
    """

    def __init__(self, cfg, dparams, backend: str = "jnp",
                 max_slots: int = 4, max_len: int = 64,
                 prefill_len: Optional[int] = None,
                 sampling: smp.SamplingParams = smp.GREEDY, seed: int = 0,
                 page_size="auto", num_pages: Optional[int] = None,
                 prefix_sharing="auto", kv_bits=None, speculate_k: int = 0,
                 draft_dparams=None, draft_kv_bits=None, mesh=None,
                 heartbeat_timeout: float = 2.0):
        from repro.models import serving
        self.cfg, self.dparams, self.backend = cfg, dparams, backend
        self.max_slots, self.max_len = max_slots, max_len
        # mesh=None: today's single-device engine, bit-for-bit.  With a
        # (data, model) mesh the context owns placement (weights by the
        # sharding rules, caches along the slot/page axis, scheduler state
        # replicated) and its data-axis size doubles as the host fleet for
        # the heartbeat/drain story below.
        self.mesh_ctx = shd.MeshContext(mesh)
        self.speculate_k = int(speculate_k)
        if self.speculate_k < 0:
            raise ValueError("speculate_k must be >= 0")
        if self.speculate_k:
            if not serving.supports_speculative(cfg):
                raise ValueError(
                    f"family {cfg.family!r} cannot serve speculatively "
                    "(serving.supports_speculative): rewinding to the "
                    "accepted length needs position-addressed cache writes")
            if isinstance(draft_kv_bits, (list, tuple)):
                draft_kv_bits = tuple(int(b) for b in draft_kv_bits)
            serving.kv_specs(cfg, draft_kv_bits)
        else:
            draft_kv_bits = None
            draft_dparams = None
        self.draft_kv_bits = draft_kv_bits
        # self-draft by default: the verifier proposes for itself — the
        # degenerate case the greedy parity tests pin (every proposal
        # accepted, output bit-identical to the baseline engine)
        self.draft_dparams = (dparams if (self.speculate_k
                                          and draft_dparams is None)
                              else draft_dparams)
        if self.mesh_ctx.is_active:
            self.dparams = self.mesh_ctx.put_params(self.dparams)
            if self.draft_dparams is dparams:
                # self-draft: keep sharing the verifier's placed weights
                self.draft_dparams = self.dparams
            elif self.draft_dparams is not None:
                self.draft_dparams = self.mesh_ctx.put_params(
                    self.draft_dparams)
        # normalize to a hashable jit-key component and resolve eagerly: an
        # unpackable feature axis raises HERE (engine construction), never
        # inside a jitted launch
        if isinstance(kv_bits, (list, tuple)):
            kv_bits = tuple(int(b) for b in kv_bits)
        serving.kv_specs(cfg, kv_bits)
        self.kv_bits = kv_bits
        self.prefill_len = prefill_len or max_len // 2
        if self.prefill_len > max_len:
            raise ValueError("prefill_len exceeds the slot ring max_len")

        if page_size == "auto":
            page_size = auto_page_size(cfg, max_len, self.prefill_len)
        if page_size is not None:
            if not serving.supports_paging(cfg):
                raise ValueError(f"family {cfg.family!r} has no ring axis "
                                 "to page (pass page_size=None)")
            if max_len % page_size or self.prefill_len % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide both max_len "
                    f"{max_len} and prefill_len {self.prefill_len}")
        self.page_size = page_size
        self.pages_per_slot = (0 if page_size is None
                               else max_len // page_size)
        self.n_prompt_pages = (0 if page_size is None
                               else self.prefill_len // page_size)
        if prefix_sharing == "auto":
            prefix_sharing = page_size is not None and cfg.family == "dense"
        elif prefix_sharing:
            if page_size is None:
                raise ValueError("prefix_sharing requires a paged cache")
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"prefix_sharing unavailable for family {cfg.family!r}: "
                    "its generation depends on inputs the token-keyed radix "
                    "index cannot see (prefix embeds / frames / recurrent "
                    "state)")
        self.prefix_sharing = bool(prefix_sharing)

        self.sampling = sampling
        fns = _engine_jits(cfg, backend, sampling, page_size, kv_bits,
                           speculate_k=self.speculate_k,
                           draft_kv_bits=draft_kv_bits,
                           mesh=self.mesh_ctx.mesh)
        self._admit_fn, self._step_fn = fns["admit"], fns["step"]
        self._logits_fn = fns["logits"]
        if self.speculate_k:
            self._draft_fn = fns["draft_step"]
            self._verify_fn = fns["verify"]

        if page_size is None:
            self.pool = None
            self._pages = None
            self.caches = serving.init_caches(cfg, max_slots, max_len,
                                              kv_bits=kv_bits)
        else:
            user_pages = num_pages is not None
            if num_pages is None:
                num_pages = 1 + max_slots * self.pages_per_slot
            if num_pages < 2:
                raise ValueError("num_pages must be >= 2 (NULL page + one "
                                 "allocatable page)")
            # auto-sized pools round up so the physical-page axis divides
            # the data axis; an explicit num_pages is honored verbatim
            # (cache_shardings falls back to replication if it won't shard)
            self.pool = PagePool(num_pages, page_size,
                                 prefix_sharing=self.prefix_sharing,
                                 pad_to=(1 if user_pages
                                         else self.mesh_ctx.data))
            self._pages = np.full((max_slots, self.pages_per_slot),
                                  NULL_PAGE, np.int32)
            self.caches = serving.init_paged_caches(cfg, max_slots,
                                                    self.pool.num_pages,
                                                    page_size,
                                                    kv_bits=kv_bits)
            mask = serving.paged_leaf_mask(cfg)
            leaves = zip(jax.tree_util.tree_leaves(mask),
                         jax.tree_util.tree_leaves(self.caches))
            self._page_bytes = sum(t.nbytes // t.shape[1]
                                   for m, t in leaves if m)
        self._reserved = 0              # pages promised to live slots
        self._suppress = np.zeros(max_slots, bool)

        if self.speculate_k:
            if page_size is None:
                self.draft_caches = serving.init_caches(
                    cfg, max_slots, max_len, kv_bits=draft_kv_bits)
                self._draft_pages = None
                self._draft_num_pages = 0
            else:
                # private draft pool behind a STATIC identity page table:
                # slot i owns pages [1 + i*pps, 1 + (i+1)*pps) forever — no
                # allocator, no sharing, nothing to release.  Rewind after a
                # rejected proposal is the same masked-overwrite contract as
                # the verifier pool: entries above the accepted position are
                # never read (``<= pos`` masks) and the next round's writes
                # land on them in order.
                dnp = 1 + max_slots * self.pages_per_slot
                self.draft_caches = serving.init_paged_caches(
                    cfg, max_slots, dnp, page_size, kv_bits=draft_kv_bits)
                self._draft_num_pages = dnp
                self._draft_pages = jnp.asarray(
                    1 + np.arange(max_slots * self.pages_per_slot,
                                  dtype=np.int32).reshape(
                                      max_slots, self.pages_per_slot))
        # one pending catch-up token per slot: fed to the draft at pos-1
        # before the next round's proposals (set when a round accepts all k
        # — the draft never consumed its own last token — or when a
        # suppressed-slot fallback tick advanced the verifier without it)
        self._catchup = np.zeros(max_slots, bool)
        self._catchup_tok = np.zeros(max_slots, np.int64)

        self.tokens = jnp.zeros((max_slots, 1), jnp.int32)
        if self.mesh_ctx.is_active:
            self.caches = self.mesh_ctx.put_caches(self.caches)
            self.tokens = self.mesh_ctx.put_replicated(self.tokens)
            if self.speculate_k:
                self.draft_caches = self.mesh_ctx.put_caches(
                    self.draft_caches)
                if self._draft_pages is not None:
                    self._draft_pages = self.mesh_ctx.put_replicated(
                        self._draft_pages)
        self._pos = np.zeros(max_slots, np.int64)
        self._live = np.zeros(max_slots, bool)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self.queue: List[int] = []
        self._pending: Dict[int, Request] = {}
        self._finished: List[RequestOutput] = []
        self._next_rid = 0
        self._submitted_s: Dict[int, float] = {}
        self._key = jax.random.PRNGKey(seed)
        self.stats = dict(prefill_launches=0, decode_launches=0,
                          useful_tokens=0, occupancy_sum=0.0,
                          prefix_hits=0, zero_prefill_admits=0,
                          cached_tokens=0, deferred_admissions=0,
                          evictions=0, pages_peak=0, draft_launches=0,
                          verify_launches=0, spec_rounds=0,
                          accepted_tokens=0, host_drains=0,
                          drained_requests=0, admitted=0, queue_wait_s=0.0,
                          prefill_tokens=0, prefill_rows=0,
                          prefill_slot_tokens=0, admit_ticks=0,
                          gc_pause_s=0.0, gc_collections=0)
        self.stats.update({f"{k}_{p}_s": 0.0 for k in _TICK_KINDS
                           for p in _PHASES})
        self._ticks = 0
        self._gc0 = (_GCPauses.pause_s, _GCPauses.collections)
        self._plan: Optional[_Span] = None
        self._attrs: dict = {}
        # -- host liveness (drain-on-death) --------------------------------
        # The data axis doubles as the host fleet: host h owns the
        # contiguous slot range fault.owned_slots(h, max_slots, n_hosts).
        # The engine beats every non-failed host once per step() on a tick
        # clock; a host declared dead by the heartbeat has its slots'
        # requests drained back to the front of the admission queue (pages
        # freed through the normal refcount path) and its slots retired.
        self.n_hosts = self.mesh_ctx.data
        self.heartbeat = fault.Heartbeat(list(range(self.n_hosts)),
                                         timeout_s=heartbeat_timeout)
        self._hb_clock = 0
        self._failed_hosts: set[int] = set()
        self._dead_slots = np.zeros(max_slots, bool)
        self._requests: Dict[int, Request] = {}

    # -- request lifecycle ---------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request for admission; returns its request id."""
        with self._span("engine.submit", rid=self._next_rid):
            rid = self._enqueue(request)
            self._submitted_s[rid] = _clock()
        return rid

    def _enqueue(self, request: Request) -> int:
        rid = self._next_rid
        toks = np.asarray(request.tokens)
        if toks.ndim != 1:
            raise ValueError(
                f"request {rid}: prompt must be a 1-D array of token ids; "
                f"got shape {toks.shape}")
        if not np.issubdtype(toks.dtype, np.integer):
            raise ValueError(
                f"request {rid}: prompt dtype {toks.dtype} is not an "
                "integer type — token ids would be silently truncated")
        L = int(toks.shape[0])
        if not 1 <= L <= self.prefill_len:
            raise ValueError(f"request {rid}: prompt length {L} not in "
                             f"[1, prefill_len={self.prefill_len}]")
        if request.max_tokens < 1:
            raise ValueError(f"request {rid}: max_tokens must be >= 1")
        worst = (0 if self.pool is None
                 else -(-(L + request.max_tokens - 1) // self.page_size))
        if (L + request.max_tokens - 1 > self.max_len
                or (self.pool is not None and worst > self.pool.capacity)):
            budget = (f"slot rings {self.max_slots} x {self.max_len}"
                      if self.pool is None else
                      f"needs {worst} pages of {self.page_size} tokens, "
                      f"pages free {self.pool.available}"
                      f"/{self.pool.capacity}")
            raise ValueError(
                f"request {rid}: prompt_len {L} + max_tokens "
                f"{request.max_tokens} overflows the slot ring "
                f"(max_len={self.max_len}; {budget})")
        if self.cfg.family == "vlm" and self.cfg.n_prefix_tokens:
            # the first n_prefix_tokens positions ARE the image context
            # (prefill swaps them for prefix_embeds); a shorter prompt would
            # gather its logits inside the prefix region and let decode
            # ring-writes overwrite it, and a missing embed array would be
            # zero-filled — a silently different model input
            if L <= self.cfg.n_prefix_tokens:
                raise ValueError(
                    f"vlm prompt length {L} must exceed n_prefix_tokens="
                    f"{self.cfg.n_prefix_tokens} (the prefix-embed region)")
            if "prefix_embeds" not in request.extras:
                raise ValueError(
                    "vlm requests need extras['prefix_embeds'] — the "
                    "admission batch would otherwise swap the prefix "
                    "region for zeros")
        if self.cfg.family == "audio" and "frames" not in request.extras:
            raise ValueError(
                "audio requests need extras['frames'] (encoder input) — "
                "an empty slot row would cross-attend to an all-zero "
                "encoder and decode garbage")
        self._next_rid += 1
        self._pending[rid] = request
        # retained past admission so a host drain can requeue in-flight
        # requests verbatim (dropped again when the request finishes)
        self._requests[rid] = request
        self.queue.append(rid)
        return rid

    def collect(self) -> List[RequestOutput]:
        """Drain and return the finished request outputs."""
        out, self._finished = self._finished, []
        return out

    @property
    def live_slots(self) -> int:
        return int(self._live.sum())

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self._live.any())

    def compile_counts(self) -> dict:
        """Jit-cache sizes of the two engine executables (recompile guard:
        after a warmup trace these must never grow — same-shaped launches
        forever, the whole point of the fixed-width slot pool)."""
        out = {"admit": self._admit_fn._cache_size(),
               "step": self._step_fn._cache_size()}
        if self.speculate_k:
            out["draft"] = self._draft_fn._cache_size()
            out["verify"] = self._verify_fn._cache_size()
        return out

    # -- KV residency metrics ------------------------------------------------
    def kv_bytes_dense(self) -> int:
        """Bytes the dense ``(max_slots, max_len)`` cache pool holds
        resident for this config at THIS engine's ``kv_bits`` policy — the
        paged engine's baseline (packed layouts price their packed bytes)."""
        from repro.models import serving
        tree = jax.eval_shape(
            lambda: serving.init_caches(self.cfg, self.max_slots,
                                        self.max_len, kv_bits=self.kv_bits))
        return sum(int(np.prod(t.shape)) * np.dtype(t.dtype).itemsize
                   for t in jax.tree_util.tree_leaves(tree))

    def kv_bytes_resident(self) -> int:
        """KV bytes currently holding live or reusable data: pages in use
        (referenced + radix-resident) plus the always-resident per-slot
        leaves (hybrid SSM state, audio cross caches).  Dense mode: the
        whole pool."""
        if self.pool is None:
            return self.kv_bytes_dense()
        total = sum(t.nbytes for t in jax.tree_util.tree_leaves(self.caches))
        paged_total = self._page_bytes * self.pool.num_pages
        return (total - paged_total) + self._page_bytes * self.pool.in_use

    def kv_bytes_peak(self) -> int:
        """High-water resident KV bytes over the engine's lifetime — the
        benchmark's memory headline (``pages_peak`` priced in bytes)."""
        if self.pool is None:
            return self.kv_bytes_dense()
        total = sum(t.nbytes for t in jax.tree_util.tree_leaves(self.caches))
        paged_total = self._page_bytes * self.pool.num_pages
        return (total - paged_total) + \
            self._page_bytes * self.stats["pages_peak"]

    def _note_pool(self) -> None:
        self.stats["evictions"] = self.pool.evictions
        self.stats["pages_peak"] = max(self.stats["pages_peak"],
                                       self.pool.in_use)

    # -- tracing -------------------------------------------------------------
    def _span(self, name: str, key: Optional[str] = None, **attrs) -> _Span:
        return _Span(self.stats, name, key, **attrs)

    def _end_plan(self, kind: str, admitted=()) -> None:
        """Close this step's ``engine.plan`` span under ``kind``; the
        launch, sync and record spans that follow carry its attributes."""
        self._attrs = dict(tick=self._ticks, kind=kind,
                           live=self.live_slots,
                           admitted=" ".join(str(r) for r in admitted))
        self._plan.close(f"{kind}_plan_s", **self._attrs)

    def _phase(self, phase: str) -> _Span:
        return self._span(f"engine.{phase}",
                          f"{self._attrs['kind']}_{phase}_s", **self._attrs)

    # -- scheduler ticks -----------------------------------------------------
    def step(self) -> dict:
        """One scheduler tick; see :meth:`_step`.  Times its phases into
        ``stats`` and brings the garbage collector's totals up to date."""
        self._ticks += 1
        try:
            with self._span("engine.plan", tick=self._ticks) as plan:
                self._plan = plan
                return self._step()
        finally:
            self._plan = None
            self.stats["gc_pause_s"] = _GCPauses.pause_s - self._gc0[0]
            self.stats["gc_collections"] = (_GCPauses.collections
                                            - self._gc0[1])

    def _step(self) -> dict:
        """One scheduler tick = one host sync.

        Admission has priority: if any slot is free, requests are queued
        and (paged mode) the head of the queue passes the page-reservation
        gate, refill (one width-1 prefill launch per admitted request;
        fully-cached prompts admit with NO launch).  Otherwise run one
        decode tick —
        a speculative round when ``speculate_k`` > 0 — over the live
        slots.  Returns a small stats dict (``kind`` in {"prefill",
        "cached", "decode", "speculative", "idle"}).
        """
        self._hb_clock += 1
        for h in range(self.n_hosts):
            if h not in self._failed_hosts:
                self.heartbeat.beat(h, self._hb_clock)
        for h in self.heartbeat.check(self._hb_clock):
            self._drain_host(h)
        free = [i for i, s in enumerate(self._slots)
                if s is None and not self._dead_slots[i]]
        if self.queue and free:
            out = self._admit_tick(free)
            if out is not None:
                return out
        if self._live.any():
            return (self._speculative_tick() if self.speculate_k
                    else self._decode_tick())
        return {"kind": "idle"}

    def _next_key(self):
        if self.sampling.kind == "greedy":
            return self._key                     # unused by argmax
        self._key, k = jax.random.split(self._key)
        return k

    def _plan_admission(self, toks: np.ndarray, max_tokens: int):
        """Page plan for one request, or None if it must wait.

        Returns ``(matched, full_hit, worst)``.  The gate keeps the
        invariant ``pool.available >= self._reserved`` — ``available``
        counts free + radix-resident (evictable) pages and residency is
        closed under prefix descendants, so a passing admission can take
        its prompt pages NOW and every future lazy decode allocation of
        every live slot is guaranteed to succeed.  Reviving a matched
        resident page consumes it from ``available``, hence the ``+ r``.
        """
        T = self.page_size
        L = len(toks)
        matched = self.pool.match_prefix(toks) if self.prefix_sharing else []
        m = len(matched)
        full_hit = self.prefix_sharing and m > 0 and m * T >= L - 1
        worst = -(-(L + max_tokens - 1) // T)
        r = sum(self.pool.is_resident(p) for p in matched)
        if self.pool.available - self._reserved < (worst - m) + r:
            return None
        return matched, full_hit, worst

    def _admit_tick(self, free: List[int]) -> Optional[dict]:
        """Admit queued requests into free slots: one ``(1, prefill_len)``
        prefill launch per admitted request that needs one, in slot order,
        each consuming the caches the previous one returned; fully-cached
        prompts admit with no launch.  Paged mode walks the queue strictly
        FIFO and stops at the first request the page gate rejects
        (head-of-line waits; decode keeps draining pages).  One host sync
        for the whole tick.  Returns None when nothing was admitted so
        ``step`` falls through to a decode tick."""
        T = self.page_size
        plans = {}
        if self.pool is None:
            take = self.queue[:len(free)]
        else:
            take = []
            for rid in self.queue[:len(free)]:
                req = self._pending[rid]
                plan = self._plan_admission(
                    np.asarray(req.tokens, np.int32), req.max_tokens)
                if plan is None:
                    self.stats["deferred_admissions"] += 1
                    break
                matched, full_hit, worst = plan
                toks = np.asarray(req.tokens, np.int32)
                L = toks.shape[0]
                # take the pages NOW: shared first (so they cannot be
                # evicted by our own fresh allocations), then fresh prompt
                # pages; decode pages stay reserved, mapped lazily.
                self.pool.acquire(matched)
                if full_hit:
                    row = list(matched)
                else:
                    n_prompt = -(-L // T)
                    row = list(matched) + self.pool.alloc(n_prompt -
                                                          len(matched))
                    # publish the full prompt pages BEFORE the launch: a
                    # same-tick duplicate prompt becomes a full hit whose
                    # shared reads happen only in later decode ticks,
                    # after this tick's prefill wrote the pages.
                    self.pool.index_prompt(toks, row[:L // T])
                self._reserved += worst - len(row)
                plans[rid] = (matched, full_hit, worst, row)
                take.append(rid)
            if not take:
                return None
        del self.queue[:len(take)]

        launches: List[tuple] = []      # each admission launch's request row
        prefilled: List[int] = []       # slots whose first token it samples
        boot: List[tuple] = []          # (slot, last prompt token)
        now = _clock()
        self.stats["admitted"] += len(take)
        self.stats["queue_wait_s"] += sum(now - self._submitted_s[rid]
                                          for rid in take)
        for slot, rid in zip(free, take):
            req = self._pending.pop(rid)
            toks = np.asarray(req.tokens, np.int32)
            L = toks.shape[0]
            self._live[slot] = True
            self._catchup[slot] = False
            if self.pool is None:
                self._slots[slot] = _Slot(rid, L, req.max_tokens, req.eos_id,
                                          now)
                self._pos[slot] = L
                launches.append(self._prefill_row(slot, toks, req.extras))
                prefilled.append(slot)
                continue
            matched, full_hit, worst, row = plans[rid]
            self._pages[slot, :len(row)] = row
            self._slots[slot] = _Slot(rid, L, req.max_tokens, req.eos_id,
                                      now, worst=worst, mapped=len(row))
            self.stats["prefix_hits"] += bool(matched)
            self.stats["cached_tokens"] += len(matched) * T
            if full_hit:
                # zero-prefill admission: every needed prompt position but
                # (at most) the last is cached; bootstrap the slot from the
                # last prompt token — its first decode tick writes that
                # token's KV (or suppresses the write for one tick if even
                # it is cached) and samples the first output token.
                self.stats["zero_prefill_admits"] += 1
                self._pos[slot] = L - 1
                self._suppress[slot] = len(matched) * T == L
                boot.append((slot, int(toks[-1])))
                if self.speculate_k:
                    # the draft pool never prefix-shares: its prefill still
                    # runs, the verifier writes no page
                    launches.append(self._prefill_row(slot, toks, req.extras))
            else:
                self._pos[slot] = L
                # prefill writes only the pages this slot OWNS: matched
                # prefix pages stay read-only (their bits are already
                # identical), the tail past ceil(L/T) stays dropped.
                launches.append(self._prefill_row(
                    slot, toks, req.extras,
                    [(j, row[j]) for j in range(len(matched), len(row))]))
                prefilled.append(slot)
        if self.pool is not None:
            self._note_pool()

        keys = [self._next_key() for _ in launches]
        self._end_plan("admit", take)
        if launches:
            with self._phase("launch"):
                for args, key in zip(launches, keys):
                    out = self._admit_fn(*self._admit_args(args), key)
                    if self.speculate_k:
                        self.tokens, self.caches, self.draft_caches = out
                    else:
                        self.tokens, self.caches = out
            P = self.prefill_len
            self.stats["admit_ticks"] += 1
            self.stats["prefill_launches"] += len(launches)
            self.stats["useful_tokens"] += len(prefilled)
            # admission padding: prompt tokens against the rows launched,
            # and against one full-width (max_slots, prefill_len) launch
            self.stats["prefill_tokens"] += sum(self._slots[s].prompt_len
                                                for s in prefilled)
            self.stats["prefill_rows"] += len(launches) * P
            self.stats["prefill_slot_tokens"] += self.max_slots * P
        with self._phase("sync"):
            if boot:
                tok_np = np.asarray(self.tokens).copy()
                for slot, last in boot:
                    tok_np[slot, 0] = last
                self.tokens = jnp.asarray(tok_np)
            tok_np = np.asarray(self.tokens[:, 0])
        with self._phase("record"):
            for slot in prefilled:
                self._record(slot, int(tok_np[slot]))
        return {"kind": "prefill" if launches else "cached",
                "admitted": list(take)}

    def _prefill_row(self, slot: int, toks: np.ndarray, extras=None,
                     pages: Sequence[tuple] = ()) -> tuple:
        """One request's part of an admission launch's arguments: its
        ``(1, prefill_len)`` right-padded row (and ``(1, ...)`` rows of
        the family's extras), its length, its slot, and in paged mode the
        ``(n_prompt_pages,)`` physical page of each prompt page the
        verifier writes, given as ``(index, page)`` ``pages``; every other
        entry carries the out-of-bounds sentinel.  A speculative paged
        engine adds the draft's pages, which it always writes."""
        L = len(toks)
        row = np.zeros((1, self.prefill_len), np.int32)
        row[0, :L] = toks
        batch = {"tokens": row}
        if self.cfg.family == "audio":
            batch["frames"] = np.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model), np.float32)
        if self.cfg.family == "vlm" and self.cfg.n_prefix_tokens:
            batch["prefix_embeds"] = np.zeros(
                (1, self.cfg.n_prefix_tokens, self.cfg.d_model), np.float32)
        for k, v in (extras or {}).items():
            batch[k][0] = v
        out = ({k: jnp.asarray(v) for k, v in batch.items()},
               jnp.asarray([L], jnp.int32), jnp.asarray(slot, jnp.int32))
        if self.pool is None:
            return out
        wp = np.full(self.n_prompt_pages, self.pool.num_pages, np.int32)
        for j, page in pages:
            wp[j] = page
        out += (jnp.asarray(wp),)
        if self.speculate_k:
            n = -(-L // self.page_size)
            dwp = np.full(self.n_prompt_pages, self._draft_num_pages,
                          np.int32)
            dwp[:n] = 1 + slot * self.pages_per_slot + np.arange(n)
            out += (jnp.asarray(dwp),)
        return out

    def _admit_args(self, row: tuple) -> tuple:
        """An admission launch's arguments, less the key: the weights, the
        request's ``row`` (:meth:`_prefill_row`) and the engine's current
        tokens and caches."""
        batch, lens, slot, *pages = row
        if self.speculate_k:
            return (self.dparams, self.draft_dparams, batch, lens, slot,
                    self.tokens, self.caches, self.draft_caches, *pages)
        return (self.dparams, batch, lens, slot, self.tokens, self.caches,
                *pages)

    def lower_admit(self):
        """The engine's jitted admission lowered at its one shape: one
        request's ``(1, prefill_len)`` row into slot 0."""
        row = self._prefill_row(0, np.zeros(1, np.int32))
        return self._admit_fn.lower(*self._admit_args(row), self._key)

    def _decode_args(self):
        """The live slots and the decode launch's arguments, less the
        sampling key; maps the page under each live slot's write
        position first (idempotent until the tick runs)."""
        live = self._live.copy()
        if self.pool is not None:
            # lazily map the page under each live slot's write position —
            # the reservation gate guarantees this allocation succeeds
            for slot in np.nonzero(live)[0]:
                pidx = int(self._pos[slot]) // self.page_size
                if self._pages[slot, pidx] == NULL_PAGE:
                    (pg,) = self.pool.alloc(1)
                    self._pages[slot, pidx] = pg
                    self._slots[slot].mapped += 1
                    self._reserved -= 1
            self._note_pool()
        live_write = live & ~self._suppress
        args = (self.dparams, self.tokens, self.caches,
                jnp.asarray(self._pos, jnp.int32), jnp.asarray(live_write))
        if self.pool is not None:
            args += (jnp.asarray(self._pages),)
        return live, args

    def decode_logits(self, tokens=None) -> jnp.ndarray:
        """``(max_slots, vocab)`` logits of the next decode tick, from
        the engine's decode program on its current state, without
        advancing it.  ``tokens (max_slots, 1)`` replaces the pending
        tokens, so two engines can score the same input."""
        _, args = self._decode_args()
        if tokens is not None:
            tok = np.asarray(tokens, np.int32)
            if self.mesh_ctx.is_active:
                tok = self.mesh_ctx.put_replicated(tok)
            args = (args[0], tok) + args[2:]
        return self._logits_fn(*args)[0]

    def lower_step(self):
        """The engine's jitted decode step lowered at its current state
        (``.compile().as_text()`` shows the program a tick runs)."""
        _, args = self._decode_args()
        return self._step_fn.lower(*args, self._key)

    def _decode_tick(self, kind: str = "decode") -> dict:
        """One decode launch over the live slots; its phases count under
        ``kind`` (a speculative round's fallback tick passes its own)."""
        live, args = self._decode_args()
        key = self._next_key()
        self._end_plan(kind)
        with self._phase("launch"):
            self.tokens, self.caches = self._step_fn(*args, key)
        with self._phase("sync"):
            tok_np = np.asarray(self.tokens[:, 0])
        with self._phase("record"):
            self.stats["decode_launches"] += 1
            n_live = int(live.sum())
            self.stats["useful_tokens"] += n_live
            self.stats["occupancy_sum"] += n_live / self.max_slots
            self._pos[live] += 1
            self._suppress[live] = False
            for slot in np.nonzero(live)[0]:
                self._record(int(slot), int(tok_np[slot]))
        return {"kind": "decode", "live": n_live}

    def _drain_catchup(self, live: np.ndarray) -> None:
        """Feed every pending catch-up token to the draft at ``pos - 1`` in
        ONE batched draft launch (its logits predict a position already
        emitted — discarded): afterwards the draft ring covers every
        position below each slot's frontier."""
        mask = self._catchup & live
        if not mask.any():
            return
        toks = np.asarray(self.tokens).copy()
        toks[mask, 0] = self._catchup_tok[mask]
        pos = self._pos.copy()
        pos[mask] -= 1
        args = (self.draft_dparams, jnp.asarray(toks), self.draft_caches,
                jnp.asarray(pos, jnp.int32), jnp.asarray(mask))
        if self.pool is not None:
            args += (self._draft_pages,)
        _, _, self.draft_caches = self._draft_fn(*args, self._next_key())
        self.stats["draft_launches"] += 1
        self._catchup[mask] = False

    def _speculative_tick(self) -> dict:
        """One speculative round: [catch-up draft] + k draft launches + ONE
        (k+1)-wide verify launch; every live slot emits 1..k+1 tokens.

        The draft proposes d_1..d_k from the last emitted token t at
        position p (each single-token launch also writes the draft's KV);
        the verify launch feeds ``[t, d_1..d_k]`` at positions ``p..p+k``
        through the multi-token decode path and fuses rejection sampling
        (greedy: longest argmax-prefix match, so the emitted stream is the
        baseline verifier stream token for token — for ANY draft).  Both
        caches rewind by masked overwrite: rejected positions are above the
        new frontier, never read, and overwritten in order next round.

        Suppressed slots (full-prefix-hit boot, first tick) fall back to a
        baseline decode tick for everyone: their write position lives in a
        shared read-only radix page, which the W-wide batched scatter
        cannot skip per-position; the draft catches up next round.  The
        fallback's catch-up launch and token read count as its plan phase.
        """
        live = self._live.copy()
        k = self.speculate_k
        if (live & self._suppress).any():
            self._drain_catchup(live)
            fed = {int(s): int(np.asarray(self.tokens)[s, 0])
                   for s in np.nonzero(live)[0]}
            out = self._decode_tick("speculative")
            for s, t in fed.items():
                if self._slots[s] is not None:  # draft missed this token
                    self._catchup[s] = True
                    self._catchup_tok[s] = t
            return out
        if self.pool is not None:
            # map every verifier page the verify scatter can land on (up to
            # the slot's write budget — beyond it the entries stay NULL and
            # the writes drop); all within the reserved worst-case pages,
            # so these allocations are guaranteed to succeed
            T = self.page_size
            for slot in np.nonzero(live)[0]:
                st = self._slots[slot]
                p = int(self._pos[slot])
                last = min(p + k, st.prompt_len + st.max_tokens - 2)
                for pidx in range(p // T, last // T + 1):
                    if self._pages[slot, pidx] == NULL_PAGE:
                        (pg,) = self.pool.alloc(1)
                        self._pages[slot, pidx] = pg
                        st.mapped += 1
                        self._reserved -= 1
            self._note_pool()
        self._end_plan("speculative")
        with self._phase("launch"):
            self._drain_catchup(live)
            live_j = jnp.asarray(live)
            pos0 = self._pos.copy()
            cur = self.tokens
            dtoks, dlgs = [], []
            for j in range(k):
                args = (self.draft_dparams, cur, self.draft_caches,
                        jnp.asarray(pos0 + j, jnp.int32), live_j)
                if self.pool is not None:
                    args += (self._draft_pages,)
                cur, row, self.draft_caches = self._draft_fn(
                    *args, self._next_key())
                self.stats["draft_launches"] += 1
                dtoks.append(cur)
                dlgs.append(row)
            draft_toks = jnp.concatenate(dtoks, axis=1)           # (B, k)
            draft_logits = jnp.stack(dlgs, axis=1)                # (B, k, V)
            tokens_w = jnp.concatenate([self.tokens, draft_toks], axis=1)
            args = (self.dparams, tokens_w, self.caches,
                    jnp.asarray(pos0, jnp.int32), live_j)
            if self.pool is not None:
                args += (jnp.asarray(self._pages),)
            accepted, out_tokens, self.caches = self._verify_fn(
                *args, draft_toks, draft_logits, self._next_key())
        with self._phase("sync"):
            acc = np.asarray(accepted)
            out_np = np.asarray(out_tokens)
            tok_np = np.asarray(self.tokens).copy()
        with self._phase("record"):
            self.stats["verify_launches"] += 1
            self.stats["spec_rounds"] += 1
            n_live = int(live.sum())
            self.stats["occupancy_sum"] += n_live / self.max_slots
            for slot in np.nonzero(live)[0]:
                m = int(acc[slot])
                self.stats["accepted_tokens"] += m
                for j in range(m + 1):
                    self._record(int(slot), int(out_np[slot, j]))
                    self.stats["useful_tokens"] += 1
                    if self._slots[slot] is None:   # finished mid-round:
                        break                       # the rest drops
                self._pos[slot] += m + 1
                if self._slots[slot] is not None:
                    tok_np[slot, 0] = out_np[slot, m]
                    if m == k:
                        # all accepted: the draft sampled d_k but never fed
                        # it — its KV at position p+k is owed before next
                        # round
                        self._catchup[slot] = True
                        self._catchup_tok[slot] = int(out_np[slot, k - 1])
            self.tokens = jnp.asarray(tok_np)
        return {"kind": "speculative", "live": n_live,
                "accepted": [int(a) for a in acc[live]]}

    def _record(self, slot: int, token: int) -> None:
        st = self._slots[slot]
        st.generated.append(token)
        if len(st.generated) == 1:
            st.first_token_s = _clock()
        done_len = len(st.generated) >= st.max_tokens
        done_eos = st.eos_id is not None and token == st.eos_id
        if done_len or done_eos:
            reason = "eos" if done_eos else "length"
            with self._span("engine.finish", rid=st.rid,
                            tokens=len(st.generated), reason=reason):
                self._finish(slot, st, reason)

    def _finish(self, slot: int, st: _Slot, reason: str) -> None:
        self._finished.append(RequestOutput(
            rid=st.rid, tokens=np.asarray(st.generated, np.int32),
            prompt_len=st.prompt_len, finish_reason=reason,
            submitted_s=self._submitted_s.pop(st.rid),
            admitted_s=st.admitted_s, first_token_s=st.first_token_s,
            finished_s=_clock()))
        if self.pool is not None:
            row = self._pages[slot]
            self.pool.release(int(p) for p in row if p != NULL_PAGE)
            self._pages[slot, :] = NULL_PAGE
            self._reserved -= st.worst - st.mapped
            self._note_pool()
        self._slots[slot] = None
        self._live[slot] = False
        self._catchup[slot] = False
        self._requests.pop(st.rid, None)

    # -- host failure / drain ------------------------------------------------
    def fail_host(self, host: int) -> None:
        """Stop beating ``host``; the heartbeat declares it dead after
        ``timeout_s`` ticks and ``step()`` drains its slots (failure
        injection for tests and ``launch/serve.py --fail-host``)."""
        if not 0 <= host < self.n_hosts:
            raise ValueError(f"host {host} outside fleet of {self.n_hosts}")
        self._failed_hosts.add(host)

    def _drain_host(self, host: int) -> None:
        """Retire a dead host's slots and requeue their requests.

        Pages go back through the normal refcount release path, the slots
        are excluded from future admission, and the drained requests
        rejoin the FRONT of the admission queue (rid order) so surviving
        hosts replay them from scratch — greedy decoding makes the replay
        token-identical to an uninterrupted run.
        """
        drained = []
        for slot in fault.owned_slots(host, self.max_slots, self.n_hosts):
            self._dead_slots[slot] = True
            st = self._slots[slot]
            if st is None:
                continue
            if self.pool is not None:
                row = self._pages[slot]
                self.pool.release(int(p) for p in row if p != NULL_PAGE)
                self._pages[slot, :] = NULL_PAGE
                self._reserved -= st.worst - st.mapped
                self._note_pool()
            self._slots[slot] = None
            self._live[slot] = False
            self._suppress[slot] = False
            self._catchup[slot] = False
            drained.append(st.rid)
        for rid in sorted(drained):
            self._pending[rid] = self._requests[rid]
        self.queue = sorted(drained) + [r for r in self.queue
                                        if r not in drained]
        self.stats["host_drains"] += 1
        self.stats["drained_requests"] += len(drained)
        if self._dead_slots.all() and (self.queue or self._live.any()):
            raise fault.HostFailure(host)

    # -- whole-trace driver --------------------------------------------------
    def run(self, requests: Sequence[Request],
            arrivals: Optional[Sequence[int]] = None
            ) -> Dict[object, RequestOutput]:
        """Serve a trace to completion; returns outputs keyed by the
        request's index in ``requests``.

        ``arrivals``: optional per-request arrival times in scheduler
        ticks (default: all at tick 0 — the synchronized case).  A request
        is submitted the first tick at/after its arrival; the loop runs
        idle ticks while waiting on future arrivals.

        Requests that were ``submit()``-ed directly before this call also
        finish under the loop; since they have no index in ``requests``,
        their outputs come back under the string key ``f"rid:{rid}"``
        instead of clashing with (or crashing on) the positional keys.
        """
        arrivals = ([0] * len(requests) if arrivals is None
                    else [int(a) for a in arrivals])
        if len(arrivals) != len(requests):
            raise ValueError("arrivals and requests length mismatch")
        order = sorted(range(len(requests)), key=lambda i: (arrivals[i], i))
        rid_to_idx: Dict[int, int] = {}
        outs: Dict[object, RequestOutput] = {}
        nxt, t = 0, 0
        while nxt < len(order) or self.has_work():
            while nxt < len(order) and arrivals[order[nxt]] <= t:
                i = order[nxt]
                rid_to_idx[self.submit(requests[i])] = i
                nxt += 1
            self.step()
            for out in self.collect():
                if out.rid in rid_to_idx:
                    outs[rid_to_idx[out.rid]] = out
                else:           # submitted before run(): key by request id
                    outs[f"rid:{out.rid}"] = out
            t += 1
        return outs
