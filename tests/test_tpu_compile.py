"""The main path's Pallas kernels compile for a TPU v5e (Mosaic), at the
widths the chip runs them, without a chip.

Each test lowers one kernel with ``interpret=False`` for one device of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler
installed here: what Mosaic refuses (unaligned blocks, unsupported shape
casts, a non-32-bit MXU accumulator) fails here instead of on the chip.
Nothing runs, so these say nothing about results or speed; the interpret-
mode parity tests check the numbers.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and the test workers
all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import decode_attention as datt
from repro.kernels import int8_matmul as i8
from repro.kernels import quant_matmul as qmk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _kernels_in(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _lm_schedule(arch, c_out, tile_n=128):
    """The static tile schedule ``init_deployed_linear`` builds for one
    ``c_out``-wide site of ``arch`` (each precision group padded to whole
    tiles)."""
    cfg = get_config(arch)
    sizes = cfg.deploy.group_sizes(c_out, sorted(cfg.quant.weight_bits))
    return tuple(b for b, n in sizes.items()
                 for _ in range(-(-n // tile_n)))


# (name, Kp, tile_bits, tile_n, M, compute dtype): an MLPerf-Tiny dae-ad
# site (640 inputs, one 128-wide tile per bit-width, a serving batch), the
# largest fused contraction, a decode-shaped bf16 call, and mamba2-780m's
# in_proj (d_model 1536 -> 6448 channels, 51 tiles) at prefill shape.
FUSED_2D = [
    ("dae-ad-640", 640, (2, 4, 8), 128, 16, jnp.float32),
    ("k-single-step-max", qmk.K_SINGLE_STEP_MAX, (2, 4, 4, 8), 128, 128,
     jnp.float32),
    ("decode-bf16", qmk.K_SINGLE_STEP_MAX, (4, 8), 128, 8, jnp.bfloat16),
    ("mamba2-in-proj", 1536, _lm_schedule("mamba2-780m", 6448), 128, 128,
     jnp.bfloat16),
]


def _fused_width(tile_bits, Kp):
    return qmk.fused_tile_offsets(tile_bits, Kp)[-1] + Kp // (
        8 // tile_bits[-1])


@pytest.mark.parametrize("name,Kp,tile_bits,tile_n,M,cd", FUSED_2D,
                         ids=[c[0] for c in FUSED_2D])
def test_fused_2d_compiles(spec, name, Kp, tile_bits, tile_n, M, cd):
    T, W = len(tile_bits), _fused_width(tile_bits, Kp)
    fn = lambda x, p, s: qmk.quant_matmul_fused_2d(
        x, p, s, tile_bits, Kp=Kp, tile_n=tile_n, bm=min(M, 128),
        interpret=False, compute_dtype=cd)
    assert _kernels_in(fn, spec((M, Kp), cd), spec((tile_n, W), jnp.uint8),
                       spec((T * tile_n,), jnp.float32)) == 1


# (name, E, Kp, tile_bits, tile_n, M): an E=8 stack at the largest fused
# contraction; deepseek-v3's expert down projection (moe_d_ff 2048 ->
# d_model 7168, 56 tiles), whose resident packed buffer alone is 7.5 MiB
# per expert; and a narrow stack whose 8-wide tiles all share one step.
FUSED_3D = [
    ("E8-k-single-step-max", 8, qmk.K_SINGLE_STEP_MAX, (2, 4, 8, 8), 128,
     64),
    ("deepseek-expert-down", 8, 2048, _lm_schedule("deepseek-v3-671b", 7168),
     128, 128),
    ("narrow-E4", 4, 36, (2, 2, 4, 4, 4, 8, 8), 8, 8),
]


@pytest.mark.parametrize("name,E,Kp,tile_bits,tile_n,M", FUSED_3D,
                         ids=[c[0] for c in FUSED_3D])
def test_fused_3d_expert_stack_compiles(spec, name, E, Kp, tile_bits,
                                        tile_n, M):
    T, W = len(tile_bits), _fused_width(tile_bits, Kp)
    fn = lambda x, p, s: qmk.quant_matmul_fused_3d(
        x, p, s, tile_bits, Kp=Kp, tile_n=tile_n, bm=M, interpret=False)
    assert _kernels_in(fn, spec((E, M, Kp), jnp.float32),
                       spec((E, tile_n, W), jnp.uint8),
                       spec((E, T * tile_n), jnp.float32)) == 1


# qwen1.5-4b per-group sites: attention/FFN-up inputs (K = d_model = 2560)
# and the FFN down projection (K = d_ff = 6912) at 4 bits, prefill (M=128)
# and decode (M=8) shaped.
PER_GROUP = [(2560, 8, 128), (2560, 2, 8), (6912, 4, 128), (6912, 4, 8)]


@pytest.mark.parametrize("K,bits,M", PER_GROUP,
                         ids=[f"K{k}-b{b}-M{m}" for k, b, m in PER_GROUP])
def test_per_group_quant_matmul_compiles(spec, K, bits, M):
    f = 8 // bits
    N = 2560
    bk = qmk.pick_bk(K, f)
    fn = lambda x, p, s: qmk.quant_matmul_2d(
        x, p, s, bits, bm=M, bn=128, bk=bk, interpret=False,
        compute_dtype=jnp.bfloat16)
    assert _kernels_in(fn, spec((M, K), jnp.bfloat16),
                       spec((N, K // f), jnp.uint8),
                       spec((N,), jnp.float32)) == 1


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_decode_attention_compiles(spec, kv_bits):
    """qwen1.5-4b decode: 20 kv-heads of 128 channels, a 1024-deep ring."""
    B, KV, rep, hd, S = 4, 20, 1, 128, 1024
    nb = hd * kv_bits // 8
    fn = lambda q, kp, ks, vp, vs, pos: datt.decode_attention(
        q, kp, ks, vp, vs, pos, (kv_bits,), (hd,), out_dtype=jnp.bfloat16,
        interpret=False)
    ring, scales = spec((B, KV, S, nb), jnp.uint8), \
        spec((B, KV, S, 1), jnp.float32)
    assert _kernels_in(fn, spec((B, KV, rep, hd), jnp.float32), ring,
                       scales, ring, scales, spec((B,), jnp.int32)) == 1


def test_scaled_int8_mm_compiles(spec):
    fn = lambda a, b, sa, sb: i8.scaled_int8_mm(a, b, sa, sb,
                                                interpret=False)
    assert _kernels_in(fn, spec((256, 2560), jnp.int8),
                       spec((512, 2560), jnp.int8),
                       spec((256,), jnp.float32),
                       spec((512,), jnp.float32)) == 1
