"""Smoke run of the search -> deploy -> serve path, compiled, on a TPU.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: phase B on a
                                      # (data=2, model=2) mesh against the
                                      # meshless engine on one of them

Phase A is the paper's pipeline on MLPerf-Tiny models at their published
sizes: dae-ad runs a few warmup, search and finetune steps, deploys and
serves through the fused packed kernels; resnet8-cifar10 deploys random
NAS logits and serves its packed convs.  Each served output is compared
with the frozen (fake-quant) reference.

Phase B is a server answering requests: a ``ServingEngine`` over
qwen1.5-4b at its published widths (random seeded weights, packed KV cache
at 8 bits, paged).  The engine's first decode tick's logits are checked
against a plain-jnp-backend engine over the same requests on the same
chip.

Every kernel runs compiled: ``repro.kernels.interpret_mode`` picks the
Pallas interpreter only off a TPU, and each phase prints the number of
Mosaic kernels (``tpu_custom_call``) in its compiled step, which must not
be zero.  Nothing here catches an exception: a failed phase or comparison
exits non-zero.  On success the last line of stdout is one JSON object
naming the device.  The phase functions take their sizes as arguments so
the tests can drive them at reduced sizes on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.api import Engine, PrecisionPolicy              # noqa: E402
from repro.api.scheduler import Request, ServingEngine     # noqa: E402
from repro.config import get_config                        # noqa: E402
from repro.core import search                              # noqa: E402
from repro.data import pipeline as pipe                    # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh               # noqa: E402
from repro.models import serving, tinyml                   # noqa: E402

# |served - frozen| / max(1, |frozen|) for the MLPerf-Tiny phase.  Both
# sides run f32 arithmetic at "highest" matmul precision, so they differ by
# f32 roundoff; an 8-bit activation step flipped by that roundoff moves an
# output by about one step, well below this bound.
TINY_TOL = 1e-2
# ||pallas - jnp|| / ||jnp|| over the logits of qwen1.5-4b: both backends
# compute in bf16 and round differently (scale before vs after the dot,
# fused vs jnp attention), and the difference compounds over the depth.
LOGITS_TOL = 5e-2


def custom_calls(compiled) -> int:
    """Mosaic kernels in a compiled program: the proof a kernel ran
    compiled (the Pallas interpreter lowers to plain HLO instead)."""
    return compiled.as_text().count("tpu_custom_call")


def _compare_tiny(eng, batch) -> dict:
    policy = PrecisionPolicy.deployed("pallas")
    served_fn = jax.jit(lambda dp, b: eng.apply_fn(dp, None, policy, b)
                        ).lower(eng.deployed_params, batch).compile()
    served = served_fn(eng.deployed_params, batch)
    frozen = jax.jit(lambda p, n, b: eng.apply_fn(
        p, n, PrecisionPolicy.FROZEN, b))(eng.params, eng.nas, batch)
    err = float(jnp.max(jnp.abs(served - frozen)))
    scale = max(1.0, float(jnp.max(jnp.abs(frozen))))
    if not err <= TINY_TOL * scale:
        raise AssertionError(f"|served - frozen| = {err} exceeds "
                             f"{TINY_TOL} * {scale}")
    return {"err": err, "scale": scale,
            "tpu_custom_call": custom_calls(served_fn)}


def phase_a(n_samples: int = 64, batch_size: int = 16,
            search_epochs: int = 2) -> dict:
    """MLPerf-Tiny at published sizes: dae-ad through search -> finetune
    -> deploy -> fused Pallas serve, resnet8 through packed-conv serve."""
    out = {}
    with jax.default_matmul_precision("highest"):
        cfg = tinyml.TINY_CONFIGS["dae-ad"]
        settings = search.SearchSettings(
            cfg=cfg.quant, objective="size", lam=3e-5, warmup_epochs=1,
            search_epochs=search_epochs, finetune_epochs=1)
        eng = Engine.for_tinyml(cfg, settings, key=jax.random.PRNGKey(0))
        data = pipe.SyntheticTiny(cfg, n=n_samples, seed=0)
        epochs = lambda: data.batches(batch_size)
        t0 = time.perf_counter()
        eng.search(epochs).finetune(epochs)
        eng.deploy()
        batch = next(iter(data.batches(batch_size, seed=7)))
        out["dae-ad"] = dict(_compare_tiny(eng, batch),
                             epochs=len(eng.history),
                             seconds=time.perf_counter() - t0)

        cfg = tinyml.TINY_CONFIGS["resnet8-cifar10"]
        eng = Engine.for_tinyml(cfg, key=jax.random.PRNGKey(1))
        eng.randomize_nas(1).deploy()
        batch = next(iter(pipe.SyntheticTiny(cfg, n=8, seed=1).batches(4)))
        out["resnet8-cifar10"] = _compare_tiny(eng, batch)
    return out


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _engine(cfg, dparams, backend: str, n_slots: int, max_len: int,
            prompt_len: int, mesh=None) -> ServingEngine:
    """The smoke's server: paged cache, 8-bit KV, one slot per request."""
    return ServingEngine(cfg, dparams, backend=backend, max_slots=n_slots,
                         max_len=max_len, prefill_len=prompt_len, kv_bits=8,
                         mesh=mesh)


def admitted(cfg, dparams, requests, backend: str, max_len: int,
             prompt_len: int) -> ServingEngine:
    """An engine on ``backend`` after its first tick: every request
    admitted by one prefill launch into the paged pool, first tokens
    pending, the first decode tick next."""
    eng = _engine(cfg, dparams, backend, len(requests), max_len, prompt_len)
    for r in requests:
        eng.submit(r)
    if eng.step()["kind"] != "prefill":
        raise AssertionError("the first tick did not admit the requests")
    return eng


def serve(cfg, dparams, requests, max_len: int, prompt_len: int,
          mesh=None) -> dict:
    """Serve ``requests`` through a paged 8-bit-KV Pallas ServingEngine:
    once to compile, once timed.  Returns the greedy tokens per request."""
    t0 = time.perf_counter()
    _engine(cfg, dparams, "pallas", len(requests), max_len, prompt_len,
            mesh).run(requests)
    warm = time.perf_counter() - t0
    eng = _engine(cfg, dparams, "pallas", len(requests), max_len,
                  prompt_len, mesh)
    t0 = time.perf_counter()
    outs = eng.run(requests)
    dt = time.perf_counter() - t0
    tokens = [np.asarray(outs[i].tokens) for i in range(len(requests))]
    return {"tokens": tokens, "warm_seconds": warm, "seconds": dt,
            "generated": int(sum(len(t) for t in tokens))}


def _model_and_trace(cfg, n_requests, prompt_lo, prompt_hi, gen, seed):
    """Seeded deployed weights (made on the device) and requests."""
    dparams = jax.jit(lambda k: serving.init_deployed_model(cfg, k))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    requests = [Request(tokens=rng.integers(0, cfg.vocab_size, (int(L),))
                        .astype(np.int32), max_tokens=gen)
                for L in rng.integers(prompt_lo, prompt_hi + 1, n_requests)]
    return dparams, requests, prompt_hi + gen


def phase_b(cfg, n_requests: int = 4, prompt_lo: int = 32,
            prompt_hi: int = 64, gen: int = 8, seed: int = 0) -> dict:
    """qwen1.5-4b served by the paged Pallas engine.  Its first decode
    tick's logits are checked against a jnp-backend engine over the same
    requests (both fed the jnp engine's first tokens), and the Mosaic
    kernels are counted in the engine's own compiled decode step."""
    dparams, requests, max_len = _model_and_trace(
        cfg, n_requests, prompt_lo, prompt_hi, gen, seed)
    ref = admitted(cfg, dparams, requests, "jnp", max_len, prompt_hi)
    eng = admitted(cfg, dparams, requests, "pallas", max_len, prompt_hi)
    first = np.asarray(ref.tokens)
    out = {"first_tokens_agree": int((np.asarray(eng.tokens) == first).sum()),
           "decode_rel_err": _rel(eng.decode_logits(first),
                                  ref.decode_logits(first)),
           "tpu_custom_call": custom_calls(eng.lower_step().compile())}
    if not out["decode_rel_err"] <= LOGITS_TOL:
        raise AssertionError(f"decode_rel_err = {out['decode_rel_err']} "
                             f"exceeds {LOGITS_TOL}")
    out.update(serve(cfg, dparams, requests, max_len, prompt_hi))
    return out


def phase_b_mesh(cfg, n_requests: int = 4, prompt_lo: int = 32,
                 prompt_hi: int = 64, gen: int = 8, seed: int = 0,
                 mesh_shape=(2, 2)) -> dict:
    """The same trace on a (data, model) mesh and on one device; greedy
    tokens must agree request for request."""
    dparams, requests, max_len = _model_and_trace(
        cfg, n_requests, prompt_lo, prompt_hi, gen, seed)
    one = serve(cfg, dparams, requests, max_len, prompt_hi)
    meshed = serve(cfg, dparams, requests, max_len, prompt_hi,
                   mesh=make_test_mesh(*mesh_shape))
    same = [bool(np.array_equal(a, b))
            for a, b in zip(one["tokens"], meshed["tokens"])]
    return {"one": one, "mesh": meshed, "identical": same}


def _print_serve(tag: str, r: dict) -> None:
    print(f"  {tag}: {r['generated']} tokens in {r['seconds']:.3f} s "
          f"({r['generated'] / r['seconds']:.1f} tokens/s; a rough figure, "
          f"not a benchmark); first run with compilation "
          f"{r['warm_seconds']:.1f} s")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cfg = get_config("qwen1.5-4b")
    print(f"phase B model: {cfg.name} at published widths "
          f"({cfg.n_layers} layers, d_model {cfg.d_model}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}); random seeded weights")

    if args.chips == 4:
        r = phase_b_mesh(cfg)
        _print_serve("meshless", r["one"])
        _print_serve("mesh (data=2, model=2)", r["mesh"])
        print(f"phase B mesh: greedy tokens identical per request: "
              f"{r['identical']}")
        if not all(r["identical"]):
            raise AssertionError("mesh and meshless greedy tokens diverge")
    else:
        a = phase_a()
        for name, r in a.items():
            print(f"phase A {name}: |served - frozen| max = {r['err']:.3e} "
                  f"(tolerance {TINY_TOL} x {r['scale']:.3f}); "
                  f"tpu_custom_call = {r['tpu_custom_call']}")
            if r["tpu_custom_call"] <= 0:
                raise AssertionError(f"phase A {name}: no compiled kernel")
        print(f"phase A dae-ad: {a['dae-ad']['epochs']} warmup/search/"
              f"finetune epochs, deploy and serve in "
              f"{a['dae-ad']['seconds']:.1f} s")
        b = phase_b(cfg)
        print(f"phase B engine vs jnp-backend engine: first tokens agree "
              f"{b['first_tokens_agree']}/{len(b['tokens'])}, first decode "
              f"logits rel err {b['decode_rel_err']:.3e} (tolerance "
              f"{LOGITS_TOL}); "
              f"engine decode step tpu_custom_call = "
              f"{b['tpu_custom_call']}")
        if b["tpu_custom_call"] <= 0:
            raise AssertionError("phase B: no compiled kernel")
        _print_serve("phase B serve", b)
        print(f"phase B sample tokens: {b['tokens'][0].tolist()}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
