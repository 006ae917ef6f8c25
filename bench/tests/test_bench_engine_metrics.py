"""The per-layer metrics that read the engine's own tracing counters:
by hand on a synthetic context, silent where the program lacks the
counters, and on the deltas of a real (tiny) engine's counters."""
import dataclasses
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402

READERS = ("decode_host_ms", "prefill_useful_share", "gc_pause_share")


@dataclasses.dataclass
class _Window:
    seconds: float


def _ctx(stats, seconds=40.0):
    return harness.Context(arch=None, peak={}, window=_Window(seconds),
                           trace=None, stats=stats, mem_peak=0)


def _read(name, ctx):
    return harness.metric_reader(name)(ctx)


def test_readers_by_hand():
    stats = {"decode_launches": 200, "decode_plan_s": 0.3,
             "decode_launch_s": 0.1, "decode_sync_s": 20.0,
             "decode_record_s": 0.2, "prefill_tokens": 242,
             "prefill_slot_tokens": 2 * 16 * 256, "gc_pause_s": 0.12}
    ctx = _ctx(stats)
    assert _read("decode_host_ms", ctx) == pytest.approx(3.0)
    assert _read("prefill_useful_share", ctx) == pytest.approx(
        100 * 242 / 8192)
    assert _read("gc_pause_share", ctx) == pytest.approx(0.3)


@pytest.mark.parametrize("name", READERS)
def test_readers_silent_without_the_counters(name):
    """A program without the counters (an older engine) reports nothing
    and does not raise; nor does a window with no tick of the kind."""
    assert _read(name, _ctx({"decode_launches": 10,
                             "occupancy_sum": 5.0})) is None
    assert _read(name, _ctx({"decode_launches": 0, "decode_plan_s": 0.0,
                             "decode_launch_s": 0.0, "decode_record_s": 0.0,
                             "prefill_tokens": 0, "prefill_slot_tokens": 0,
                             "gc_pause_s": 0.0}, seconds=0.0)) is None


def test_readers_on_engine_counter_deltas():
    """Counter deltas over a stretch of a real engine's steps, taken the
    way the harness takes them, give values in range."""
    import jax
    import numpy as np
    from repro.api.scheduler import Request, ServingEngine
    from repro.config import get_config
    from repro.models import serving
    cfg = get_config("qwen1.5-4b").reduced()
    dp = serving.init_deployed_model(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, dp, backend="jnp", max_slots=4, max_len=32,
                        prefill_len=16)
    rng = np.random.default_rng(0)
    for n in (4, 9):
        eng.submit(Request(rng.integers(0, cfg.vocab_size, n), max_tokens=5))
    eng.step()                                 # warm both programs first
    eng.step()
    s0 = dict(eng.stats)
    eng.submit(Request(rng.integers(0, cfg.vocab_size, 6), max_tokens=5))
    t0 = harness.clock()
    while eng.has_work():
        eng.step()
    seconds = harness.clock() - t0
    s1 = dict(eng.stats)
    stats = {k: s1[k] - s0.get(k, 0) for k in s1
             if isinstance(s1[k], (int, float))}
    ctx = _ctx(stats, seconds)
    assert 0 < _read("decode_host_ms", ctx) < 1e3 * seconds
    assert _read("prefill_useful_share", ctx) == pytest.approx(
        100 * 6 / (4 * 16))
    assert 0 <= _read("gc_pause_share", ctx) <= 100
