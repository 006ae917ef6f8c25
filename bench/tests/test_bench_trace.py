"""Trace reduction: kernels told apart from HLO text, busy and idle time,
per-kernel time and the breakdown, on a trace recorded on the chip."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

import trace_reduce as trr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def test_two_kernels_named_kernel_are_told_apart():
    """Both Pallas bodies are called ``_kernel``; their HLO custom calls
    (instruction name aside) differ in operands: a position vector and two
    u8 rings for decode attention, one u8 packed weight and f32 steps for
    a packed GEMM."""
    lines = (DATA / "hlo_kernels.txt").read_text()
    got = trr.kernels_of(lines)
    assert got == {"quant_matmul.154": "packed_gemm",
                   "decode_attention.7": "decode_attn",
                   "quant_matmul_fused.8": "packed_gemm",
                   "quant_matmul.19": "packed_gemm"}
    # the class comes from the operands, not the instruction's name
    renamed = lines.replace("%decode_attention.7", "%_kernel.1").replace(
        "%quant_matmul.154", "%_kernel.2")
    got = trr.kernels_of(renamed)
    assert got["_kernel.1"] == "decode_attn"
    assert got["_kernel.2"] == "packed_gemm"


def test_union_and_gaps():
    s = np.array([0.0, 2.0, 3.0, 10.0])
    e = np.array([4.0, 3.0, 5.0, 11.0])
    assert trr.union_seconds(s, e) == 6.0
    assert trr.gaps(s, e, 0.0, 12.0) == [(5.0, 10.0), (11.0, 12.0)]


def _synthetic():
    gemm = ('%quant_matmul.1 = f32[16,8]{1,0} custom-call(%a, %b, %c), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[16,64]{1,0}, u8[8,32]{1,0}, f32[1,8]{1,0}}, x')
    attn = ('%decode_attention.2 = bf16[2,1,1,8]{3,2,1,0} custom-call(%p, '
            '%q, %k, %ks, %v, %vs), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={s32[2]{0}, f32[2,1,1,8]{3,2,1,0}, '
            'u8[2,1,16,8]{3,2,1,0}, f32[2,1,16,1]{3,2,1,0}, u8[2,1,16,8]'
            '{3,2,1,0}, f32[2,1,16,1]{3,2,1,0}}, x')
    ops = [("%while.3 = (s32[]) while(%t)", 100, 400),
           (gemm, 110, 200), (attn, 200, 300), ("%fusion.4 = f32[2] x", 300,
                                                 400),
           (gemm, 700, 800), ("%copy.5 = f32[2] copy(%y)", 950, 1300)]
    spans = [("window", 0, 1000), ("decode_tick", 50, 450),
             ("collect", 450, 500), ("admit_tick", 600, 900),
             ("submit", 900, 950)]
    return trr.Events({0: ops}, spans)


def test_reduce_synthetic_by_hand():
    r = trr.reduce_events(_synthetic())
    assert r.window_s == pytest.approx(1000e-9)
    # busy: [100, 400] + [700, 800] + [950, 1000] (clipped to the window)
    assert r.busy_s == pytest.approx(450e-9)
    assert r.kernel_s["packed_gemm"] == pytest.approx(190e-9)
    assert r.kernel_s["decode_attn"] == pytest.approx(100e-9)
    ops = dict(r.breakdown["device_ops"])
    assert "while" not in ops and ops["quant_matmul"] == pytest.approx(190e-9)
    gaps = r.breakdown["idle_gaps"]
    # [400, 700] falls between collect and admit_tick; [800, 950] inside
    # admit_tick; [0, 100] inside decode_tick (each named at its middle)
    assert gaps == [["other", pytest.approx(300e-9)],
                    ["admit_tick", pytest.approx(150e-9)],
                    ["decode_tick", pytest.approx(100e-9)]]


def _recorded():
    raw = json.loads(gzip.decompress((DATA / "trace_small.json.gz")
                                     .read_bytes()))
    ops = {int(k): [tuple(o) for o in v] for k, v in raw["ops"].items()}
    return raw, trr.Events(ops, [tuple(s) for s in raw["spans"]])


def test_reduce_recorded_trace():
    """A slice of a qwen1.5-4b decode window recorded on one TPU v5e."""
    raw, ev = _recorded()
    r = trr.reduce_events(ev)
    assert 0 < r.busy_s <= r.window_s
    assert r.kernel_s["packed_gemm"] > 0 and r.kernel_s["decode_attn"] > 0
    assert r.kernel_s["packed_gemm"] + r.kernel_s["decode_attn"] < r.busy_s
    assert len(r.breakdown["device_ops"]) <= 10
    assert len(r.breakdown["idle_gaps"]) <= 10
    names = {g[0] for g in r.breakdown["idle_gaps"]}
    assert names <= set(trr.SPANS) | {"other"}
    for key, want in raw["expect"].items():
        got = r.busy_s if key == "busy_s" else r.kernel_s[key]
        assert got == pytest.approx(want, rel=1e-9)
