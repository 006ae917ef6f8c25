"""One run of one cell: set-up, pre-roll, a measured window, the check.

The harness drives the program's ``ServingEngine`` through its public
calls (``submit``, ``step``, ``collect``) on its own wall clock.  A step
ends in a host sync on the sampled tokens, so the end of a tick is when its
tokens exist: an admission tick (``kind == "prefill"``) gives each admitted
request its first token, a decode tick gives one token to every request
that is live.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and ``metrics/<metric>.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

import seeded as S
import stats as st
import traffic as tr

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
clock = time.perf_counter


class RunError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad spec)."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    workload: dict
    arch: S.Arch                # the configuration file's record
    config: dict                # the whole configuration file
    mix: dict                   # the traffic file
    limits: dict                # the cell's correctness limits
    end_to_end: list            # metric entries this cell reports
    per_layer: list


def _reports(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{wl['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" /
                         f"{name}.json").read_text())
    return Cell(wl, S.served_arch(config["arch"]), config, mix, limits,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def program_config(cell: Cell):
    """The program's configuration of the cell's architecture, with the
    file's values where the program takes them as options (the RoPE base,
    the padded vocabulary), checked against every other size the
    configuration file states."""
    from repro.config import get_config
    a = cell.arch
    cfg = dataclasses.replace(get_config(cell.config["program_arch"]),
                              vocab_size=a.vocab_size)
    if a.family == "dense":
        cfg = dataclasses.replace(cfg, rope_theta=a.rope_theta)
    want = {"n_layers": a.num_hidden_layers, "d_model": a.hidden_size,
            "vocab_size": a.vocab_size, "family": a.family,
            "compute_dtype": a.compute_dtype,
            "tie_embeddings": bool(a.get("tie_embeddings",
                                         a.get("tie_word_embeddings")))}
    if a.family == "dense":
        want.update(n_heads=a.num_attention_heads,
                    n_kv_heads=a.num_key_value_heads, head_dim=a.head_dim,
                    d_ff=a.intermediate_size, rope_theta=a.rope_theta,
                    qkv_bias=a.qkv_bias)
    else:
        want.update(ssm_state=a.state_size, ssm_head_dim=a.ssm_head_dim,
                    ssm_expand=a.expand)
    got = {k: getattr(cfg, k) for k in want}
    got["weight_bits"] = tuple(sorted(cfg.quant.weight_bits))
    got["deploy_fractions"] = tuple(cfg.deploy.fractions)
    got["deploy_align"] = cfg.deploy.align
    want["weight_bits"] = tuple(sorted(a.weight_bits))
    want["deploy_fractions"] = tuple(a.deploy_fractions)
    want["deploy_align"] = a.deploy_align
    bad = {k: (want[k], got[k]) for k in want if want[k] != got[k]}
    if bad:
        raise RunError(f"program config differs from {a}: {bad}")
    return cfg


# ---------------------------------------------------------------------------
# Driving the engine
# ---------------------------------------------------------------------------

class Request:
    __slots__ = ("rid", "due", "prompt", "max_tokens", "times", "done",
                 "tokens", "client", "in_prefill")

    def __init__(self, rid, due, prompt, max_tokens, client=None):
        self.rid, self.due, self.client = rid, due, client
        self.prompt, self.max_tokens = prompt, max_tokens
        self.times: List[float] = []
        self.done = None
        self.tokens = None
        self.in_prefill = False


class Loop:
    """The client side of the loop: submits requests when due, steps the
    engine, stamps every token with the end of the tick that made it."""

    def __init__(self, eng, gen: tr.Generator, mix: dict, span):
        self.eng, self.gen, self.mix, self.span = eng, gen, mix, span
        self.open = mix["loop"] != "closed"
        self.reqs: Dict[int, Request] = {}
        self.live: set = set()
        self.ticks: List[tuple] = []     # (kind, t0, t1, rows, contexts)
        self.mismatched = 0
        self.lag = []                    # open loop: submit - due
        self._origin = None
        self._pending = None

    def _submit(self, req: tr.Req, due: float, client=None):
        from repro.api.scheduler import Request as EngineRequest
        with self.span("submit"):
            rid = self.eng.submit(EngineRequest(tokens=req.prompt,
                                                max_tokens=req.max_tokens))
        self.reqs[rid] = Request(rid, due, req.prompt, req.max_tokens,
                                 client)
        return rid

    def start(self):
        now = clock()
        if self.open:
            self._origin = now
            self._pending = self.gen.next()
        else:
            for c in range(int(self.mix["clients"])):
                self._submit(self.gen.next(), now, client=c)

    def _arrivals(self, now):
        while self._pending is not None and \
                self._origin + self._pending.due <= now:
            due = self._origin + self._pending.due
            self._submit(self._pending, due)
            self.lag.append(clock() - due)
            self._pending = self.gen.next()

    def schedule_time(self) -> float:
        return clock() - self._origin

    def tick(self, deadline: float):
        """Submit what is due, then one engine step, or a wait for the next
        arrival (never past ``deadline``)."""
        now = clock()
        if self.open:
            self._arrivals(now)
        eng = self.eng
        if not eng.has_work():
            if not self.open:
                raise RunError("closed loop with no work")
            with self.span("wait_arrival"):
                wake = min(self._origin + self._pending.due, deadline)
                time.sleep(max(0.0, wake - clock()))
            return
        admits = bool(eng.queue) and eng.live_slots < eng.max_slots
        live_before = list(self.live)
        contexts = [len(self.reqs[r].prompt) + len(self.reqs[r].times)
                    for r in live_before]
        with self.span("admit_tick" if admits else "decode_tick"):
            t0 = clock()
            out = eng.step()
            t1 = clock()
        kind = out["kind"]
        if kind == "prefill":
            lens = []
            for rid in out["admitted"]:
                r = self.reqs[rid]
                r.in_prefill = True
                r.times.append(t1)
                self.live.add(rid)
                lens.append(len(r.prompt))
            self.ticks.append(("prefill", t0, t1, lens, None))
        elif kind == "cached":
            self.live.update(out["admitted"])
            self.ticks.append(("cached", t0, t1, [], None))
        elif kind == "decode":
            for rid in live_before:
                self.reqs[rid].times.append(t1)
            self.ticks.append(("decode", t0, t1, len(live_before), contexts))
        with self.span("collect"):
            done = eng.collect()
        for o in done:
            r = self.reqs[o.rid]
            r.done, r.tokens = t1, np.asarray(o.tokens)
            self.live.discard(o.rid)
            if len(r.times) == len(r.tokens) + 1 and r.in_prefill:
                r.times.pop(0)   # a whole-prompt cache hit: no prefill token
            if len(r.times) != len(r.tokens):
                self.mismatched += 1
            if not self.open:
                self._submit(self.gen.next(), clock(), client=r.client)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Options:
    """What a test or ``calibrate.py`` may change about a run: skip the
    look for a chip, serve a smaller architecture or mix, wrap the engine
    (a planted fault), read controls, leave the compile cache off."""
    require_tpu: bool = True
    arch_override: Optional[dict] = None
    mix_override: Optional[dict] = None
    program_cfg: Optional[object] = None
    engine_hook: Optional[object] = None
    controls: tuple = ()
    compile_cache: bool = True
    log: object = sys.stderr


def _device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"no TPU: JAX sees {devs[0].platform}; refusing to "
                       "run")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peaks(kind: str, require_tpu: bool) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        if require_tpu:
            raise RunError(f"device kind {kind!r} is not in bench/peaks.json")
        kind = next(iter(table))    # tests on the CPU: counts only
    p = table[kind]
    return {"flops": p["bf16_flops"], "bytes": p["hbm_bytes_per_s"],
            "hbm": p["hbm_bytes"]}


def _compile_cache(jax) -> bool:
    """JAX's persistent compilation cache at the fixed ``<checkout>/
    .jax_cache``, every program kept; True when it starts empty."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    cold = not any(cache.iterdir())
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cold


def _null_span(name):
    return contextlib.nullcontext()


def run(name: str, seed: int, seconds: float, trace: bool,
        opts: Options = Options(), t_start: Optional[float] = None) -> dict:
    t_start = clock() if t_start is None else t_start
    cell = load_cell(name)
    if opts.arch_override:
        cell.arch = S.Arch({**cell.arch, **opts.arch_override})
    if opts.mix_override:
        cell.mix = {**cell.mix, **opts.mix_override}
    import jax
    device = _device_info(jax, int(cell.workload["chips"]), opts.require_tpu)
    cold = opts.compile_cache and _compile_cache(jax)
    peak = _peaks(device["kind"], opts.require_tpu)

    import loader
    from repro.api.scheduler import ServingEngine
    arch, mix = cell.arch, cell.mix
    cfg = opts.program_cfg or program_config(cell)
    dparams = loader.program_params(arch, cfg, seed)
    try:
        loader.check_layout(cfg, dparams)
    except ValueError as e:
        raise RunError(str(e)) from None
    e = mix["engine"]
    kv_bits = arch.get("kv_bits") if arch.family == "dense" else None
    eng = ServingEngine(cfg, dparams, backend="pallas",
                        max_slots=e["max_slots"], max_len=e["max_len"],
                        prefill_len=e["prefill_len"], kv_bits=kv_bits)
    if opts.engine_hook is not None:
        opts.engine_hook(eng)

    span = _null_span
    if trace:
        span = jax.profiler.TraceAnnotation

    _warm(eng, arch, seed)
    kernels = _decode_kernels(eng) if trace else None
    compiled = eng.compile_counts()

    gen = tr.Generator(mix, seed, arch.vocab_size)
    drv = Loop(eng, gen, mix, span)
    drv.start()
    first = set(drv.reqs)
    t_pre = clock()
    if drv.open:
        while drv.schedule_time() < mix["pre_roll_s"]:
            drv.tick(drv._origin + mix["pre_roll_s"])
    else:
        while any(drv.reqs[r].done is None for r in first):
            drv.tick(float("inf"))
    pre_roll_s = clock() - t_pre
    stats0 = dict(eng.stats)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir, profiler_options=_profile_options(jax))
    n_ticks0 = len(drv.ticks)
    with span("window"):
        w0 = clock()
        while clock() < w0 + seconds:
            drv.tick(w0 + seconds)
        w1 = clock()
    if trace:
        jax.profiler.stop_trace()
    stats1 = dict(eng.stats)
    grew = eng.compile_counts() != compiled
    mem = jax.devices()[0].memory_stats() or {}
    mem_peak = int(mem.get("peak_bytes_in_use", 0))

    window = st.Window(w0, w1, drv.reqs.values(), drv.ticks[n_ticks0:],
                       drv.lag)
    end_to_end = {
        "output_tok_per_s": window.tokens / window.seconds,
        "ttft_p90_ms": 1e3 * st.percentile(window.ttft(), 90),
        "itl_p99_ms": 1e3 * st.percentile(window.itl(), 99),
        "setup_s": w0 - t_start,
    }
    finished = [r for r in drv.reqs.values()
                if r.done is not None and w0 < r.done <= w1]
    attempted = len(window.due)
    failed = drv.mismatched

    # the program's state goes before the reference takes the chip
    eng_stats = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                 if isinstance(stats1[k], (int, float))}
    del eng, dparams, drv
    gc.collect()

    import check
    verdict = check.served(arch, seed, finished, cell.limits, mix,
                            opts.controls)

    result = {"correct": verdict["correct"] and failed == 0,
              "attempted": attempted, "failed": failed}
    log = opts.log
    print(f"cell {name} seed {seed}: pre-roll {pre_roll_s:.3f} s, window "
          f"{window.seconds:.3f} s, {len(window.ticks)} ticks, "
          f"{window.tokens} tokens, {attempted} requests due, "
          f"{len(finished)} finished, cold compile cache: {cold}", file=log)
    if window.lag:
        print(f"generator lag max {max(window.lag):.6f} s", file=log)
    if grew:
        print("COMPILED INSIDE THE WINDOW: compile counts grew", file=log)
        result["correct"] = False
    if trace:
        import trace_reduce as trr
        print(f"kernels in the decode program: {kernels}", file=log)
        try:
            red = trr.reduce(tdir, int(cell.workload["chips"]))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = Context(arch=arch, peak=peak, window=window, trace=red,
                      stats=eng_stats, mem_peak=mem_peak)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown
    else:
        metrics = {m["name"]: {"value": end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device["memory_peak_bytes"] = mem_peak
    result["metrics"] = metrics
    result["device"] = device
    for k, v in verdict["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=log)
    result["checks"] = verdict["checks"]
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    arch: S.Arch
    peak: dict
    window: st.Window
    trace: object
    stats: dict
    mem_peak: int


def _warm(eng, arch, seed):
    """Compile the cell's own admission and decode programs: one request
    admitted and decoded to its end.  The engine's jit caches then hold
    every shape the window uses."""
    from repro.api.scheduler import Request as EngineRequest
    rng = np.random.default_rng(seed)
    eng.submit(EngineRequest(
        tokens=rng.integers(0, arch.vocab_size, 8).astype(np.int32),
        max_tokens=2))
    while eng.has_work():
        eng.step()
    eng.collect()


def _decode_kernels(eng) -> dict:
    """Kernel classes of the decode program the window runs, from its
    compiled HLO: how many of each the trace should show per tick."""
    import trace_reduce as trr
    counts: dict = {}
    for c in trr.kernels_of(eng.lower_step().compile().as_text()).values():
        counts[c] = counts.get(c, 0) + 1
    return counts


def _profile_options(jax):
    """Device ops and the harness's spans; no Python function tracing,
    which would slow the host loop being measured."""
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o
