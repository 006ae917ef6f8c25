"""Traffic generator and window statistics, on the CPU."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[2] / "src")]

import stats  # noqa: E402
import traffic as tr  # noqa: E402

LONGGEN = {"loop": "closed", "clients": 16,
           "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.4,
                          "min": 32, "max": 128},
           "output_len": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                          "min": 128, "max": 1024}}
POISSON = dict(LONGGEN, loop="poisson", rate_per_s=4.0,
               shared_prefix={"count": 4, "len": 64, "share": 0.6,
                              "min_suffix": 16})
BURST = dict(LONGGEN, loop="burst", phases=[[2.0, 10.0], [8.0, 2.0]])
SEEDS = (0, 7, 2**31 + 11, 2**33 + 5)


@pytest.mark.parametrize("mix", [LONGGEN, POISSON, BURST],
                         ids=["closed", "poisson", "burst"])
def test_same_seed_same_schedule(mix):
    a = tr.Generator(mix, 2**32 + 3, 1000).take(300)
    b = tr.Generator(mix, 2**32 + 3, 1000).take(300)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_tokens, x.due) == (y.max_tokens, y.due)
    c = tr.Generator(mix, 2**32 + 4, 1000).take(300)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_within_clips(seed):
    for r in tr.Generator(LONGGEN, seed, 1000).take(2 * tr.POOL):
        assert 32 <= len(r.prompt) <= 128
        assert 128 <= r.max_tokens <= 1024
        assert ((r.prompt >= 0) & (r.prompt < 1000)).all()


@pytest.mark.parametrize("mix", [LONGGEN, POISSON, BURST],
                         ids=["closed", "poisson", "burst"])
def test_every_seed_gets_the_same_sizes(mix):
    """Every seed gets the same stream of sizes, arrivals and shared
    prefixes in the same order; the seed draws only the token ids (the
    first `clients` outputs of a closed loop are its staggered starts)."""
    streams = []
    for seed in SEEDS:
        reqs = tr.Generator(mix, seed, 1000).take(2 * tr.POOL)
        streams.append([(len(r.prompt), r.max_tokens, r.due) for r in reqs])
    assert all(s == streams[0] for s in streams)
    n = LONGGEN["clients"]
    assert sorted(m for _, m, _ in streams[0][n:n + tr.POOL]) == \
        sorted(tr.stratified(LONGGEN["output_len"]))


def test_medians_follow_the_mix():
    reqs = tr.Generator(LONGGEN, 1, 1000).take(tr.POOL + 16)[16:]
    assert np.median([len(r.prompt) for r in reqs]) == pytest.approx(64, 2)
    assert np.median([r.max_tokens for r in reqs]) == pytest.approx(512, 8)


@pytest.mark.parametrize("seed", SEEDS)
def test_poisson_rate_holds_on_average(seed):
    reqs = tr.Generator(POISSON, seed, 1000).take(tr.POOL)
    rate = len(reqs) / reqs[-1].due
    assert rate == pytest.approx(4.0, rel=0.02)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert (gaps > 0).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_burst_cycle_holds_on_average(seed):
    g = tr.Generator(BURST, seed, 1000)
    reqs = g.take(4 * tr.POOL)
    T = reqs[-1].due
    mean_rate = (2.0 * 10.0 + 8.0 * 2.0) / 10.0
    assert len(reqs) / T == pytest.approx(mean_rate, rel=0.1)
    # arrivals crowd into the burst phase of each cycle
    phase = np.array([(r.due + g._phase) % 10.0 for r in reqs])
    in_burst = (phase < 2.0).mean()
    assert in_burst == pytest.approx(20.0 / 36.0, abs=0.06)


def test_shared_prefixes():
    reqs = tr.Generator(POISSON, 3, 1000).take(400)
    g = tr.Generator(POISSON, 3, 1000)
    heads = {tuple(p) for p in g.prefixes}
    shared = [r for r in reqs if tuple(r.prompt[:64]) in heads]
    assert 0.5 < len(shared) / len(reqs) < 0.7
    assert all(len(r.prompt) >= 80 for r in shared)


def test_closed_loop_holds_its_client_count():
    """Each client has one request in flight: the loop resubmits on
    every completion, so the engine's count of live-or-queued requests
    stays at the client count."""
    import harness

    class FakeEngine:
        max_slots = 4

        def __init__(self):
            self.queue, self.slots, self.out, self.n = [], {}, [], 0

        @property
        def live_slots(self):
            return len(self.slots)

        def has_work(self):
            return bool(self.queue or self.slots)

        def submit(self, req):
            self.queue.append((self.n, req.max_tokens))
            self.n += 1
            return self.n - 1

        def step(self):
            if self.queue and len(self.slots) < self.max_slots:
                take = self.queue[:self.max_slots - len(self.slots)]
                del self.queue[:len(take)]
                for rid, mt in take:
                    self.slots[rid] = mt - 1
                self._finish()
                return {"kind": "prefill", "admitted": [r for r, _ in take]}
            for rid in self.slots:
                self.slots[rid] -= 1
            self._finish()
            return {"kind": "decode", "live": len(self.slots)}

        def _finish(self):
            from types import SimpleNamespace
            for rid in [r for r, left in self.slots.items() if left <= 0]:
                del self.slots[rid]
                self.out.append(SimpleNamespace(rid=rid, tokens=[0] * 0))

        def collect(self):
            out, self.out = self.out, []
            return out

    mix = dict(LONGGEN, clients=6,
               output_len={"dist": "fixed", "value": 5})
    eng = FakeEngine()
    drv = harness.Loop(eng, tr.Generator(mix, 0, 100), mix,
                         harness._null_span)
    drv.start()
    for _ in range(200):
        drv.tick(float("inf"))
        assert len(eng.queue) + len(eng.slots) == 6


def test_check_sample_holds_min_tokens():
    """The sample has the longest request, at least `n` requests, and
    draws more until it holds `min_tokens` served tokens."""
    from types import SimpleNamespace

    import check
    done = [SimpleNamespace(rid=i, tokens=np.zeros(L)) for i, L in
            enumerate([300, 20, 30, 40, 50, 60, 70, 80])]
    for seed in SEEDS:
        few = check.sample(done, seed, 4)
        assert len(few) == 4 and few[0].rid == 0
        many = check.sample(done, seed, 4, min_tokens=550)
        assert [r.rid for r in many[:1]] == [0] and len(many) > 4
        assert sum(len(r.tokens) for r in many) >= 550
        assert {r.rid for r in few} <= {r.rid for r in many}
    assert len(check.sample(done, 1, 4, min_tokens=10**6)) == len(done)


def test_percentiles_over_all_samples():
    """A tail is the tail of every sample, not a mean of chunk tails."""
    v = list(range(1, 101)) + [1000] * 5
    assert stats.percentile(v, 99) == pytest.approx(np.percentile(v, 99))
    chunks = [stats.percentile(v[i:i + 21], 99) for i in range(0, 105, 21)]
    assert stats.percentile(v, 99) != pytest.approx(np.median(chunks))


def test_window_counts_waits_and_gaps():
    R = harness_request(due=1.0, times=[1.5, 1.6, 1.8, 2.6])
    late = harness_request(due=2.0, times=[])
    w = stats.Window(1.0, 2.5, [R, late], [])
    assert w.tokens == 3
    assert sorted(w.ttft()) == pytest.approx([0.5, 0.5])
    assert sorted(w.itl()) == pytest.approx([0.1, 0.2])


def harness_request(due, times):
    import harness
    r = harness.Request(0, due, np.zeros(4, np.int32), 8)
    r.times = list(times)
    return r
