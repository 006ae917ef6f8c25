"""Serving launcher: deployed mixed-precision model, request-level
continuous batching over a paged KV cache (repro.api.ServingEngine).

The deployed weights are the Sec. III-C output: channels reordered and
grouped by searched bit-width, packed sub-byte, consumed as per-precision
sub-GEMMs: ``--backend pallas`` runs the Pallas kernels of
kernels/quant_matmul.py (compiled on a TPU, interpreted elsewhere),
``--backend jnp`` the plain-jnp reference.  The
launcher synthesizes a staggered-arrival trace (requests arriving over
time with ragged prompt/output lengths) and serves it through the paged
slot pool: finished slots are reclaimed and refilled without re-jitting,
so prefill of new arrivals interleaves with decode of in-flight requests,
and KV pages of repeated prompt prefixes are shared copy-free (radix
index, ``--no-prefix-sharing`` to disable).  ``--page-size 0`` serves the
dense per-slot rings instead.  ``--lockstep`` runs the same trace
wave-at-a-time through the engine (submit a wave, drain it, repeat) — the
shortest-job-barrier baseline continuous batching removes.
``--speculate-k K`` serves speculatively: a draft proposes K tokens per
verify launch (``--draft-bits B`` re-quantizes the draft to a uniform
B-bit channel assignment — the aggressive end of the paper's channel-wise
Pareto front; default self-draft).

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --reduced \
      --requests 8 --slots 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api.scheduler import Request, ServingEngine
from repro.config import ARCH_IDS, get_config
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh, make_production_mesh
from repro.models import serving


def build_trace(cfg, args, rng):
    """Staggered-arrival synthetic trace: ragged prompts, outputs, times."""
    reqs, arrivals = [], []
    min_len = max(1, args.prompt_len // 2)
    if cfg.family == "vlm" and cfg.n_prefix_tokens:
        min_len = max(min_len, cfg.n_prefix_tokens + 1)  # past the prefix
    for i in range(args.requests):
        L = int(rng.integers(min_len, args.prompt_len + 1))
        gen = int(rng.integers(max(1, args.gen // 4), args.gen + 1))
        extras = {}
        if cfg.family == "audio":
            extras["frames"] = rng.standard_normal(
                (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        if cfg.family == "vlm" and cfg.n_prefix_tokens:
            extras["prefix_embeds"] = rng.standard_normal(
                (cfg.n_prefix_tokens, cfg.d_model)).astype(np.float32)
        reqs.append(Request(
            tokens=rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32),
            max_tokens=gen, extras=extras))
        arrivals.append(int(rng.integers(0, args.stagger + 1)))
    return reqs, arrivals


def _engine(cfg, dparams, args, mesh=None):
    page_size = {0: None, -1: "auto"}.get(args.page_size, args.page_size)
    draft = None
    if args.speculate_k and args.draft_bits:
        draft = serving.draft_model(dparams, cfg, args.draft_bits)
    return ServingEngine(cfg, dparams, backend=args.backend,
                         max_slots=args.slots,
                         max_len=args.prompt_len + args.gen,
                         prefill_len=args.prompt_len,
                         page_size=page_size,
                         num_pages=args.num_pages or None,
                         prefix_sharing=(False if args.no_prefix_sharing
                                         else "auto"),
                         speculate_k=args.speculate_k,
                         draft_dparams=draft,
                         mesh=mesh)


def _paged_line(eng):
    if eng.pool is None:
        return "paged:      off (dense slot rings)"
    st = eng.stats
    return (f"paged:      page_size {eng.page_size}, peak "
            f"{st['pages_peak']}/{eng.pool.capacity} pages, "
            f"{st['prefix_hits']} prefix hits "
            f"({st['zero_prefill_admits']} zero-prefill, "
            f"{st['cached_tokens']} cached tokens), "
            f"{st['evictions']} evictions, "
            f"{st['deferred_admissions']} deferred — resident KV "
            f"{eng.kv_bytes_resident()} B vs dense {eng.kv_bytes_dense()} B")


def _trace_line(eng):
    """The engine's tracing counters in one line: host and sync time per
    decode tick (speculative rounds included), the share of prefill rows
    that held prompt tokens, the mean queue wait and the GC pauses."""
    st = eng.stats
    ticks = st["decode_launches"] + st["spec_rounds"]
    kinds = ("decode", "speculative")
    host = sum(st[f"{k}_{p}_s"] for k in kinds
               for p in ("plan", "launch", "record"))
    sync = sum(st[f"{k}_sync_s"] for k in kinds)
    per = (lambda t: 1e3 * t / ticks) if ticks else (lambda t: 0.0)
    useful = (100.0 * st["prefill_tokens"] / st["prefill_rows"]
              if st["prefill_rows"] else 0.0)
    wait = (1e3 * st["queue_wait_s"] / st["admitted"]
            if st["admitted"] else 0.0)
    return (f"trace:      decode tick host {per(host):.2f} ms + sync "
            f"{per(sync):.2f} ms, prefill rows useful {useful:.1f}%, "
            f"queue wait {wait:.1f} ms mean, GC {st['gc_collections']} "
            f"collections {1e3 * st['gc_pause_s']:.1f} ms")


def run_continuous(cfg, dparams, reqs, arrivals, args, mesh=None):
    eng = _engine(cfg, dparams, args, mesh=mesh)
    t0 = time.time()
    if args.fail_host >= 0:
        # failure-injection drive loop: same schedule as eng.run, plus one
        # fail_host call partway through — the heartbeat then drains the
        # host's slots and the trace still completes
        order = sorted(range(len(reqs)), key=lambda i: (arrivals[i], i))
        fail_at = max(2, args.stagger // 2)
        outs, nxt, t = {}, 0, 0
        while nxt < len(order) or eng.has_work():
            while nxt < len(order) and arrivals[order[nxt]] <= t:
                i = order[nxt]
                eng.submit(reqs[i])
                nxt += 1
            if t == fail_at:
                eng.fail_host(args.fail_host)
                print(f"fail-host:  host {args.fail_host} stopped beating "
                      f"at tick {t}")
            eng.step()
            for o in eng.collect():
                outs[o.rid] = o
            t += 1
        print(f"fail-host:  {eng.stats['host_drains']} drains, "
              f"{eng.stats['drained_requests']} requests requeued, "
              f"{len(outs)}/{len(reqs)} completed")
    else:
        outs = eng.run(reqs, arrivals)
    dt = time.time() - t0
    st = eng.stats
    steps = st["prefill_launches"] + st["decode_launches"]
    occ = (st["occupancy_sum"] / st["decode_launches"]
           if st["decode_launches"] else 0.0)
    print(f"continuous: {len(outs)} requests, {st['useful_tokens']} tokens "
          f"in {dt:.2f}s ({st['useful_tokens'] / dt:.1f} tok/s) — "
          f"{st['prefill_launches']} prefills + {st['decode_launches']} "
          f"decode steps = {steps} launches, slot occupancy {occ:.2f}, "
          f"jit entries {eng.compile_counts()}")
    print(_paged_line(eng))
    print(_trace_line(eng))
    if eng.speculate_k:
        vl = steps + st["verify_launches"]  # verifier-model launches
        acc = (st["accepted_tokens"] / st["verify_launches"]
               if st["verify_launches"] else 0.0)
        print(f"speculative: k={eng.speculate_k}, {st['spec_rounds']} "
              f"rounds, {acc:.2f} drafts accepted/verify, "
              f"{st['useful_tokens'] / vl:.2f} useful tokens per "
              f"verifier launch (+{st['draft_launches']} draft launches)")
    first = outs[0]
    print("sample token ids:", first.tokens[:16])
    return dt, st["useful_tokens"]


def run_lockstep(cfg, dparams, reqs, args):
    """Wave-at-a-time baseline: submit one wave, drain it to completion,
    repeat — every wave prefills together and idles behind its longest
    request (the shortest-job barrier continuous batching removes).  Same
    engine, same executables; only the schedule differs."""
    eng = _engine(cfg, dparams, args)
    B = args.slots
    t0, useful = time.time(), 0
    for w0 in range(0, len(reqs), B):
        wave = reqs[w0:w0 + B]
        for r in wave:
            eng.submit(r)
        while eng.has_work():
            eng.step()
        useful += sum(len(o.tokens) for o in eng.collect())
    dt = time.time() - t0
    st = eng.stats
    steps = st["prefill_launches"] + st["decode_launches"]
    print(f"lockstep:   {len(reqs)} requests, {useful} useful tokens in "
          f"{dt:.2f}s ({useful / dt:.1f} tok/s) over {steps} launches")
    return dt, useful


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--stagger", type=int, default=8,
                   help="arrival window in scheduler ticks")
    p.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    p.add_argument("--page-size", type=int, default=-1,
                   help="KV page size in tokens (-1 auto, 0 dense rings)")
    p.add_argument("--num-pages", type=int, default=0,
                   help="physical page pool size (0 = default sizing)")
    p.add_argument("--no-prefix-sharing", action="store_true",
                   help="disable the radix prompt-prefix index")
    p.add_argument("--speculate-k", type=int, default=0,
                   help="speculative decoding: draft k tokens per verify "
                        "launch (0 = off)")
    p.add_argument("--draft-bits", type=int, default=0,
                   help="re-quantize the draft to this uniform channel "
                        "bit-width (0 = self-draft at full precision)")
    p.add_argument("--lockstep", action="store_true",
                   help="also run the wave-at-a-time lockstep baseline")
    p.add_argument("--production-mesh", action="store_true")
    p.add_argument("--mesh", default="",
                   help="serve on a (data, model) device mesh, e.g. "
                        "'--mesh 2,4' (needs data*model visible devices; "
                        "on CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=8). "
                        "Token-identical to the meshless engine.")
    p.add_argument("--fail-host", type=int, default=-1,
                   help="kill this data-axis host partway through the "
                        "trace (drain-on-death demo; requires --mesh)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    key = jax.random.PRNGKey(args.seed)
    dparams = serving.init_deployed_model(cfg, key)

    rng = np.random.default_rng(args.seed)
    reqs, arrivals = build_trace(cfg, args, rng)

    if args.mesh:
        # engine-owned mesh: the ServingEngine's MeshContext places the
        # weights (QTensor fused buffers sharded, the rest replicated) and
        # the caches (slot/page axis on data) itself
        dp, tp = (int(x) for x in args.mesh.split(","))
        serving_mesh = make_test_mesh(dp, tp)
        print(f"mesh:       (data={dp}, model={tp}) over "
              f"{dp * tp} of {len(jax.devices())} devices")
        run_continuous(cfg, dparams, reqs, arrivals, args,
                       mesh=serving_mesh)
        if args.lockstep:
            run_lockstep(cfg, dparams, reqs, args)
        return
    if args.fail_host >= 0:
        raise SystemExit("--fail-host requires --mesh (the data axis is "
                         "the host fleet)")

    mesh = (make_production_mesh() if args.production_mesh
            else make_test_mesh())
    rules = shd.ShardingRules(mesh)
    dparams = jax.device_put(dparams, rules.tree_shardings(dparams))

    with mesh:
        run_continuous(cfg, dparams, reqs, arrivals, args)
        if args.lockstep:
            run_lockstep(cfg, dparams, reqs, args)


if __name__ == "__main__":
    main()
