"""The harness end to end on the CPU at a tiny size: it refuses to run
without a TPU, finds new cells as data, and a broken timed path makes
``correct`` false."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402

CELLS = {"dense": "qwen4b-sharegpt-closed16",
         "ssm": "mamba2-sharegpt-closed64"}
TINY_MIX = dict(engine={"max_slots": 4, "prefill_len": 16, "max_len": 64},
                clients=4,
                prompt_len={"dist": "lognormal", "median": 8, "sigma": 0.4,
                            "min": 4, "max": 16},
                output_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                            "min": 8, "max": 40},
                check={"requests": 3, "min_tokens": 20})


def _tiny(family):
    from repro.config import DeploySpec, get_config
    if family == "dense":
        cfg = dataclasses.replace(
            get_config("qwen1.5-4b"), n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
            rope_theta=harness.load_cell(CELLS[family]).arch.rope_theta,
            deploy=DeploySpec(align=8))
        arch = dict(num_hidden_layers=2, hidden_size=64,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, intermediate_size=128, vocab_size=256,
                    deploy_align=8)
    else:
        cfg = dataclasses.replace(
            get_config("mamba2-780m"), n_layers=2, d_model=64, ssm_state=16,
            ssm_head_dim=16, vocab_size=256, ssm_chunk=8,
            deploy=DeploySpec(align=8))
        arch = dict(num_hidden_layers=2, hidden_size=64, state_size=16,
                    ssm_head_dim=16, vocab_size=256, deploy_align=8)
    return cfg, arch


TINY = {f: _tiny(f) for f in CELLS}    # one config object: one jit cache


def _run(family, hook=None, controls=(), seed=2**33 + 1, mix=TINY_MIX):
    cfg, arch = TINY[family]
    opts = harness.Options(require_tpu=False, arch_override=arch,
                           mix_override=mix, program_cfg=cfg,
                           engine_hook=hook, controls=controls,
                           compile_cache=False,
                           log=open(os.devnull, "w"))
    return harness.run(CELLS[family], seed, 1.0, False, opts)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS["dense"], "--seed", "5", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program to
    serve: no result, a non-zero exit."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS["dense"], "--seed", "5", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_cell_is_data_only(tmp_path):
    """A configuration, a traffic mix, a limit and a per-layer metric are
    added as new files and entries; the harness finds each by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "qwen1.5-4b.json").read_text())
    conf["arch"]["num_hidden_layers"] = 4
    (b / "configs" / "qwen-test.json").write_text(json.dumps(conf))
    (b / "traffic" / "mix-test.json").write_text(json.dumps(
        dict(TINY_MIX, loop="poisson", rate_per_s=2.0, pre_roll_s=1.0)))
    (b / "limits" / "cell-test.json").write_text('{"widest_gap": 1.0}')
    (b / "metrics" / "ticks_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.ticks))\n")
    spec["configs"].append({"name": "qwen-test", "source": "x",
                            "file": "bench/configs/qwen-test.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "cell-test", "config": "qwen-test",
                              "traffic": "mix-test", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves":
                              "output_tok_per_s",
                              "workloads": ["cell-test"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("cell-test", root=tmp_path)
    assert cell.arch.num_hidden_layers == 4
    assert cell.mix["loop"] == "poisson"
    assert cell.limits == {"widest_gap": 1.0}
    names = [m["name"] for m in cell.per_layer]
    assert "ticks_seen" in names and "decode_attn_roofline" not in names
    read = harness.metric_reader("ticks_seen", root=tmp_path)

    class Ctx:
        class window:
            ticks = [1, 2, 3]
    assert read(Ctx) == 3.0
    # the committed cells still load from the committed tree
    for name in CELLS.values():
        assert harness.load_cell(name).workload["name"] == name


@pytest.mark.parametrize("family", list(CELLS))
def test_sound_run_is_correct(family):
    r = _run(family)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    m = r["metrics"]
    assert list(m) == [e["name"] for e in
                       harness.load_cell(CELLS[family]).end_to_end]
    assert all(v["value"] > 0 for v in m.values())


def test_open_loop_run():
    """Poisson arrivals: requests are submitted when due, the engine idles
    between them, and every request due in the window is counted."""
    mix = dict(TINY_MIX, loop="poisson", rate_per_s=40.0, pre_roll_s=0.5)
    r = _run("dense", mix=mix)
    assert r["correct"], r["checks"]
    assert 20 <= r["attempted"] <= 80
    assert r["metrics"]["output_tok_per_s"]["value"] > 0


def _state_unchanged(eng):
    import jax
    import jax.numpy as jnp
    orig = eng._step_fn

    def step(*args):
        keep = jax.tree_util.tree_map(jnp.copy, args[2])
        tok, _ = orig(*args)
        return tok, keep
    step._cache_size = orig._cache_size
    eng._step_fn = step


def _token_altered(eng):
    orig = eng._step_fn
    V = eng.cfg.vocab_size

    def step(*args):
        tok, caches = orig(*args)
        return (tok + 1) % V, caches
    step._cache_size = orig._cache_size
    eng._step_fn = step


def _half_batch(eng):
    import jax.numpy as jnp
    orig = eng._step_fn

    def step(*args):
        tok, caches = orig(*args)
        h = tok.shape[0] // 2
        return jnp.concatenate([tok[:h], tok[:h]], axis=0), caches
    step._cache_size = orig._cache_size
    eng._step_fn = step


@pytest.mark.parametrize("family", list(CELLS))
@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch],
                         ids=["state_unchanged", "token_altered",
                              "half_batch"])
def test_broken_timed_path_is_not_correct(family, fault):
    r = _run(family, hook=fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("family", list(CELLS))
def test_control_stands_in_for_the_program(family):
    """The control (the reference with every linear's input rounded to
    fewer bits) put in the program's place reads wider gaps the fewer its
    bits, and at these tiny widths int2 fails the cell's limits.  On the
    chip, at the cells' own sizes, int8 already fails them (PERF.md)."""
    r = _run(family, controls=(2, 4, 8))
    assert not r["correct"], r["checks"]
    p = r["checks"]["program_stats"]["value"]
    c = r["checks"]["control_stats"]["value"]
    assert c[2]["mean_gap"] > c[4]["mean_gap"] > 3 * p["mean_gap"]
