"""The serving engine traced from inside: phase counters, admission padding,
per-request times, garbage-collector pauses, profiler spans, and the named
scopes of the compiled programs.

Everything runs on a tiny dense config on the CPU.  Spans are read back from
a real profiler session with ``jax.profiler.ProfileData``.
"""
import collections
import dataclasses
import gc
import glob
import os
import re
import tempfile
import time

import jax
import numpy as np
import pytest
from jax._src import source_info_util

from repro.api import scheduler as sch
from repro.api.scheduler import Request, ServingEngine
from repro.config import get_config
from repro.models import serving

_CACHE = {}


def _setup(arch="qwen1.5-4b"):
    if arch not in _CACHE:
        cfg = get_config(arch).reduced()
        dp = serving.init_deployed_model(cfg, jax.random.PRNGKey(0))
        _CACHE[arch] = (cfg, dp)
    return _CACHE[arch]


def _engine(arch="qwen1.5-4b", **kw):
    cfg, dp = _setup(arch)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_len", 16)
    return ServingEngine(cfg, dp, backend="jnp", **kw)


def _prompt(n, seed=0):
    cfg, _ = _setup()
    rng = np.random.default_rng(seed + n)
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _phase_sum(stats, kind):
    return sum(stats[f"{kind}_{p}_s"] for p in sch._PHASES)


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------

def test_admission_padding_counts_exact():
    eng = _engine()
    for n in (5, 7, 9):
        eng.submit(Request(_prompt(n), max_tokens=3))
    out = eng.step()
    assert out["kind"] == "prefill" and len(out["admitted"]) == 3
    assert eng.stats["prefill_tokens"] == 21
    assert eng.stats["prefill_slot_tokens"] == 64      # 1 x 4 slots x 16
    assert eng.stats["admitted"] == 3
    assert eng.stats["queue_wait_s"] > 0
    eng.submit(Request(_prompt(4), max_tokens=2))
    assert eng.step()["kind"] == "prefill"             # one slot was free
    assert eng.stats["prefill_tokens"] == 25
    assert eng.stats["prefill_slot_tokens"] == 128


@pytest.mark.parametrize("page_size", ["auto", None])
def test_phase_counters_bounded_by_step_wall_time(page_size):
    eng = _engine(page_size=page_size)
    for i, n in enumerate((5, 7, 9)):
        eng.submit(Request(_prompt(n), max_tokens=3 + i))
    seen = set()
    while eng.has_work():
        before = dict(eng.stats)
        t0 = time.perf_counter()
        out = eng.step()
        wall = time.perf_counter() - t0
        kind = {"prefill": "admit", "decode": "decode"}[out["kind"]]
        seen.add(kind)
        deltas = {p: eng.stats[f"{kind}_{p}_s"] - before[f"{kind}_{p}_s"]
                  for p in sch._PHASES}
        assert all(d > 0 for d in deltas.values()), (out, deltas)
        assert sum(deltas.values()) <= wall
        # the other kinds' counters did not move on this tick
        for other in set(sch._TICK_KINDS) - {kind}:
            assert _phase_sum(eng.stats, other) == _phase_sum(before, other)
    assert seen == {"admit", "decode"}


def test_request_times_are_ordered():
    eng = _engine()
    t_before = time.perf_counter()
    for i, n in enumerate((5, 7, 9, 3, 6, 8)):     # 6 requests, 4 slots
        eng.submit(Request(_prompt(n), max_tokens=2 + i % 3))
    outs = []
    while eng.has_work():
        eng.step()
        outs.extend(eng.collect())
    assert len(outs) == 6
    waits = 0.0
    for o in outs:
        assert t_before <= o.submitted_s <= o.admitted_s
        assert o.admitted_s <= o.first_token_s <= o.finished_s
        waits += o.admitted_s - o.submitted_s
    assert eng.stats["admitted"] == 6
    assert eng.stats["queue_wait_s"] == pytest.approx(waits, rel=1e-9)
    # the last two requests waited for a slot: through at least one tick
    late = sorted(outs, key=lambda o: o.rid)[4:]
    first = sorted(outs, key=lambda o: o.rid)[0]
    assert all(o.admitted_s > first.first_token_s for o in late)


def test_gc_collection_inside_a_step_is_counted():
    eng = _engine()
    eng.submit(Request(_prompt(5), max_tokens=4))
    eng.step()
    n0, s0 = eng.stats["gc_collections"], eng.stats["gc_pause_s"]
    orig = eng._step_fn

    def collecting(*args):
        gc.collect()
        return orig(*args)
    eng._step_fn = collecting
    assert eng.step()["kind"] == "decode"
    assert eng.stats["gc_collections"] >= n0 + 1
    assert eng.stats["gc_pause_s"] > s0


def test_gc_hook_installed_once():
    for _ in range(3):
        _engine()
    hooks = [cb for cb in gc.callbacks
             if getattr(cb, "__qualname__", None) == "_GCPauses.hook"]
    assert len(hooks) == 1


def test_idle_step_moves_no_phase_counter():
    eng = _engine()
    before = dict(eng.stats)
    assert eng.step() == {"kind": "idle"}
    for k in sch._TICK_KINDS:
        assert _phase_sum(eng.stats, k) == _phase_sum(before, k)
    assert "idle_ticks" not in eng.stats


# ---------------------------------------------------------------------------
# Spans in a profiler trace
# ---------------------------------------------------------------------------

def _host_events(tdir):
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return out


def test_engine_spans_in_profiler_trace():
    eng = _engine()
    eng.submit(Request(_prompt(3), max_tokens=1))
    while eng.has_work():                   # compile outside the trace
        eng.step()
    eng.collect()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation("outer_loop"):
            rids = [eng.submit(Request(_prompt(n), max_tokens=2))
                    for n in (5, 7)]
            while eng.has_work():
                eng.step()
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tdir)
    outer = [e for e in ev if e[0] == "outer_loop"]
    assert len(outer) == 1
    lo, hi = outer[0][1], outer[0][2]
    engine = [e for e in ev if e[0].startswith("engine.")]
    names = collections.Counter(e[0] for e in engine)
    assert names["engine.submit"] == 2 and names["engine.finish"] == 2
    # one admission and one decode tick, each with its four phases
    for phase in sch._PHASES:
        assert names[f"engine.{phase}"] == 2, names
    for name, s, e, stats in engine:
        assert lo <= s <= e <= hi, name
    assert sorted(st["rid"] for n, _, _, st in engine
                  if n == "engine.submit") == rids
    fin = [st for n, _, _, st in engine if n == "engine.finish"]
    assert sorted(st["rid"] for st in fin) == rids
    assert all(st["tokens"] == 2 and st["reason"] == "length" for st in fin)
    ticks = collections.defaultdict(dict)
    for n, s, e, st in engine:
        if n.split(".")[1] in sch._PHASES:
            ticks[st["tick"]][n] = (s, e, st)
    kinds = sorted(next(iter(t.values()))[2]["kind"]
                   for t in ticks.values())
    assert kinds == ["admit", "decode"]
    for phases in ticks.values():
        spans = [phases[f"engine.{p}"] for p in sch._PHASES]
        assert len({st["kind"] for _, _, st in spans}) == 1
        for (_, e0, _), (s1, _, _) in zip(spans, spans[1:]):
            assert e0 <= s1                 # consecutive, in order
        if spans[0][2]["kind"] == "admit":
            assert spans[0][2]["admitted"] == f"{rids[0]} {rids[1]}"
            assert all(st["live"] == 2 for _, _, st in spans)
    gcs = [e for e in ev if e[0] == "gc"]
    assert gcs and all("generation" in e[3] for e in gcs)


# ---------------------------------------------------------------------------
# Named scopes in the compiled programs
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+ = (.*)$")
_META = re.compile(r",? metadata=\{[^}]*\}")
_REF = re.compile(r"%[\w.\-]+")


def _instructions(hlo: str) -> collections.Counter:
    """The compiled program's instructions with names, references and
    metadata stripped: what the compiler built, not what it was called."""
    out = collections.Counter()
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            out[_REF.sub("%", _META.sub("", m.group(1)))] += 1
    return out


def _op_names(hlo: str, opcode: str) -> list:
    return [m.group(1) for line in hlo.splitlines()
            if f" {opcode}(" in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


class _NoScopes:
    """Turns every ``jax.named_scope`` (and decorator) into a no-op."""

    def __init__(self, monkeypatch):
        cls = source_info_util.ExtendNameStackContextManager
        monkeypatch.setattr(cls, "__enter__", lambda self: None)
        monkeypatch.setattr(cls, "__exit__", lambda self, *a: None)


def _compiled(eng, program):
    jax.clear_caches()
    low = eng.lower_step() if program == "decode" else eng.lower_admit()
    return low.compile().as_text()


SCOPE_CASES = [
    ("qwen1.5-4b", "decode", 8,
     {"embed", "attn", "kv_gather", "kv_write", "ffn", "lm_head", "sample"}),
    ("qwen1.5-4b", "admit", 8,
     {"embed", "attn", "kv_write", "ffn", "lm_head", "sample"}),
    ("mamba2-780m", "decode", None, {"embed", "ssm", "lm_head", "sample"}),
]


@pytest.mark.parametrize("arch,program,kv_bits,scopes", SCOPE_CASES,
                         ids=[f"{a}-{p}" for a, p, _, _ in SCOPE_CASES])
def test_named_scopes_change_only_metadata(arch, program, kv_bits, scopes,
                                           monkeypatch):
    eng = _engine(arch, kv_bits=kv_bits)
    eng.submit(Request(_prompt(5), max_tokens=4))
    eng.step()                                    # a live slot to decode
    hlo = _compiled(eng, program)
    for s in scopes:
        assert f"/{s}/" in hlo, s
    if "kv_gather" in scopes:
        gathers = _op_names(hlo, "gather")
        assert any("/kv_gather/" in n for n in gathers), gathers
    with monkeypatch.context() as m:
        _NoScopes(m)
        bare = _compiled(eng, program)
    for s in scopes:
        assert f"/{s}/" not in bare, s
    assert _instructions(hlo) == _instructions(bare)
    jax.clear_caches()
