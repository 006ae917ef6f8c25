"""``chip_smoke.py``'s phases at reduced sizes on the CPU (Pallas kernels
interpreted), so the on-chip script's control flow and comparisons are
guarded without a chip.  The compiled-kernel counts are zero here by
construction; only the chip run checks them."""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.config import get_config


@pytest.fixture(scope="module")
def cs():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def qwen_reduced():
    return get_config("qwen1.5-4b").reduced()


def test_phase_a_tiny_pipeline(cs):
    out = cs.phase_a(n_samples=32, batch_size=16, search_epochs=1)
    for name in ("dae-ad", "resnet8-cifar10"):
        assert out[name]["err"] <= cs.TINY_TOL * out[name]["scale"]
    assert out["dae-ad"]["epochs"] > 0


def test_phase_b_serves_and_matches_jnp(cs, qwen_reduced):
    out = cs.phase_b(qwen_reduced, n_requests=3, prompt_lo=5, prompt_hi=9,
                     gen=3)
    assert out["decode_rel_err"] <= cs.LOGITS_TOL
    assert out["generated"] == 3 * 3
    assert all(len(t) == 3 for t in out["tokens"])


def test_phase_b_mesh_matches_meshless(cs, qwen_reduced):
    out = cs.phase_b_mesh(qwen_reduced, n_requests=2, prompt_lo=5,
                          prompt_hi=9, gen=3, mesh_shape=(1, 1))
    assert out["identical"] == [True, True]
    assert all(len(t) == 3 for t in out["mesh"]["tokens"])


def test_main_refuses_without_a_tpu(cs, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert cs.main() != 0
    assert capsys.readouterr().out == ""        # no phase, no result line
