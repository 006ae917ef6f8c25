"""Work counts against hand counts at small shapes, and the seeded
weights against the program's own deployed tree."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import seeded as S  # noqa: E402
import work  # noqa: E402

DENSE = S.Arch(family="dense", num_hidden_layers=2, hidden_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               intermediate_size=512, vocab_size=1000, kv_bits=8,
               weight_bits=[2, 4, 8], deploy_fractions=[0.25, 0.5, 0.25],
               deploy_align=64)
SSM = S.Arch(family="ssm", num_hidden_layers=3, hidden_size=128,
             state_size=16, ssm_head_dim=32, expand=2, conv_kernel=4,
             vocab_size=500, weight_bits=[2, 4, 8],
             deploy_fractions=[0.25, 0.5, 0.25], deploy_align=64)
PEAK = {"flops": 1e12, "bytes": 1e10}


def test_group_sizes():
    assert S.group_sizes(DENSE, 256) == [(2, 64), (4, 128), (8, 64)]
    assert S.group_sizes(DENSE, 100) == [(2, 0), (4, 64), (8, 36)][1:]


def test_linear_bytes_by_hand():
    # 256 x 256: 64 rows at 2 bits, 128 at 4, 64 at 8, one f32 step each
    hand = 64 * 256 // 4 + 128 * 256 // 2 + 64 * 256 + 4 * 256
    assert S.linear_bytes(DENSE, 256, 256) == hand


def test_gemm_by_hand():
    f, b = work.gemm(DENSE, 256, 512, rows=3)
    assert f == 2 * 3 * 256 * 512
    assert b == S.linear_bytes(DENSE, 256, 512) + 3 * (256 + 512) * 2


def test_gemm_least_time_sums_each_gemm_at_its_bound():
    rows = 5
    hand = 0.0
    for ci, co in [(256, 256), (256, 128), (256, 128), (256, 256),
                   (256, 512), (256, 512), (512, 256)]:
        f = 2 * rows * ci * co
        b = S.linear_bytes(DENSE, ci, co) + rows * (ci + co) * 2
        hand += max(f / PEAK["flops"], b / PEAK["bytes"])
    hand *= 2
    f = 2 * 1 * 256 * 1000
    b = S.linear_bytes(DENSE, 256, 1000) + (256 + 1000) * 2
    hand += max(f / PEAK["flops"], b / PEAK["bytes"])
    assert work.gemm_least_s(DENSE, rows, 1, PEAK) == pytest.approx(hand)
    assert work.gemm_least_s(DENSE, 0, 0, PEAK) == 0.0


def test_decode_attention_by_hand():
    # two live slots attending to 10 and 30 positions of one layer
    f, b = work.decode_attn(DENSE, [10, 30])
    assert f == 4 * 4 * 64 * 40
    per_pos = 2 * 2 * (64 + 4)          # K and V, 2 kv heads, codes + scale
    assert b == 40 * per_pos + 2 * 4 * 64 * (4 + 2)
    least = work.decode_attn_least_s(DENSE, [10, 30], PEAK)
    assert least == pytest.approx(2 * max(f / PEAK["flops"],
                                          b / PEAK["bytes"]))


def test_model_flops_by_hand():
    lin = 2 * (256 * 256 * 2 + 256 * 128 * 2 + 256 * 512 * 3)
    assert work.token_flops(DENSE) == 2 * lin + 2 * 256 * 1000
    assert work.attn_flops(DENSE, 7) == 2 * 4 * 4 * 64 * 7
    L = 6
    hand = L * 2 * lin + 2 * 4 * 4 * 64 * (L * (L + 1) // 2) \
        + 2 * 256 * 1000
    assert work.prefill_flops(DENSE, L) == hand


def test_ssm_flops_by_hand():
    d_inner, H, N, P = 256, 8, 16, 32
    assert S.ssm_dims(SSM) == (d_inner, H, N, P)
    assert S.in_proj_width(SSM) == 2 * 256 + 2 * 16 + 8
    lin = 2 * (128 * 552 + 256 * 128)
    ssd = 4 * H * P * N + 2 * 4 * (d_inner + 2 * N)
    assert work.token_flops(SSM) == 3 * (lin + ssd) + 2 * 128 * 500
    assert work.attn_flops(SSM, 100) == 0


@pytest.mark.parametrize("arch_id", ["qwen1.5-4b", "mamba2-780m"])
def test_seeded_tree_matches_program_tree(arch_id):
    """The benchmark's weights have the structure, static data and leaf
    shapes of the program's own deployed model, at published widths
    (shapes only), as the harness's check in every run finds them; a tree
    off by one tile width is refused."""
    import dataclasses

    import jax

    import harness
    import loader
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = next(w["name"] for w in spec["workloads"]
                if w["config"] == arch_id)
    cell = harness.load_cell(name)
    cfg = harness.program_config(cell)
    mine = jax.eval_shape(lambda: loader.program_params(cell.arch, cfg,
                                                        2**40))
    loader.check_layout(cfg, mine)
    narrow = dataclasses.replace(cfg, deploy=dataclasses.replace(
        cfg.deploy, align=64))
    with pytest.raises(ValueError):
        loader.check_layout(narrow, mine)


def test_seeded_layer_is_the_same_bits_alone_and_mapped():
    """The reference regenerates one layer alone; the loader makes all
    layers under lax.map: both must give identical codes and steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    _, base = S.keys(2**35 + 9)
    mapped = jax.jit(lambda b: jax.lax.map(
        lambda i: S.canonical_layer(SSM, S.layer_key(b, i)),
        jnp.arange(3)))(base)
    alone = jax.jit(lambda b, i: S.canonical_layer(SSM, S.layer_key(b, i)))(
        base, 2)
    for x, y in zip(jax.tree_util.tree_leaves(alone),
                    jax.tree_util.tree_leaves(mapped)):
        assert np.array_equal(np.asarray(x), np.asarray(y)[2])
