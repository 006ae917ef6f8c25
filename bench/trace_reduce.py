"""From a profiler trace to busy time, kernel time and the breakdown.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops``
holds one event per HLO instruction executed, named by the instruction's
own text (``%quant_matmul.154 = f32[16,1408]... custom-call(...),
custom_call_target="tpu_custom_call", operand_layout_constraints={...}``).
The harness's spans (``TraceAnnotation``) are events of the host plane
``/host:CPU``; the span ``window`` covers the measured window exactly.

Kernels are told apart by their HLO instruction, never by the Pallas
body's name (two different kernels share the body name ``_kernel``):
a ``tpu_custom_call`` whose operands hold an s32 position vector and two
u8 rings is decode attention; one whose operands hold one u8 packed weight
and end in f32 scales is a packed-weight GEMM (per-group or fused).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

SPANS = ("submit", "admit_tick", "decode_tick", "collect", "wait_arrival")
WINDOW = "window"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")   # hold other ops' events

_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w\-]+?)(?:\.\d+)? = ")
_CONSTRAINTS = "operand_layout_constraints={"
_TYPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def instruction_stem(text: str) -> str:
    """``%quant_matmul.154 = ...`` -> ``quant_matmul``."""
    m = _NAME.match(text)
    return m.group(1) if m else text.split(" ")[0][:40]


def _operands(text: str) -> str:
    """The brace-balanced body of ``operand_layout_constraints={...}``."""
    i = text.find(_CONSTRAINTS)
    if i < 0:
        return ""
    i += len(_CONSTRAINTS)
    depth = 1
    for j in range(i, len(text)):
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return text[i:j]
    return text[i:]


def kernel_class(text: str) -> Optional[str]:
    """``"decode_attn"``, ``"packed_gemm"`` or ``"other_kernel"`` for a
    Mosaic kernel's HLO instruction; None for anything else."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    dtypes = [t for t, _ in _TYPE.findall(_operands(text))]
    n_u8 = dtypes.count("u8")
    if dtypes and dtypes[0] == "s32" and n_u8 == 2:
        return "decode_attn"
    if n_u8 == 1 and dtypes[-1] == "f32" and dtypes[0] in ("bf16", "f32"):
        return "packed_gemm"
    return "other_kernel"


def kernels_of(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> kernel class for every Mosaic kernel of a
    compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        c = kernel_class(line)
        if c is not None:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w\-.]+) = ", line)
            out[m.group(1)] = c
    return out


def union_seconds(starts: np.ndarray, ends: np.ndarray) -> float:
    """Length of the union of intervals, in the intervals' unit."""
    if starts.size == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.nonzero(new)[0]
    seg_end = np.append(reach[idx[1:] - 1], reach[-1])
    return float((seg_end - s[idx]).sum())


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """Idle intervals ``[(start, end), ...]`` of ``[lo, hi]`` outside the
    union of the intervals."""
    order = np.argsort(starts, kind="stable")
    out, t = [], lo
    for s, e in zip(starts[order], ends[order]):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class Events:
    """What the reduction needs from a trace, in nanoseconds."""
    ops: Dict[int, list]            # chip -> [(name, start, end)]
    spans: List[tuple]              # host spans [(name, start, end)]


def load(path: str) -> Events:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    spans = []
    names = set(SPANS) | {WINDOW}
    seen: Dict[str, str] = {}      # one copy of each instruction's text
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = [(seen.setdefault(e.name, e.name),
                                  e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Events(ops, spans)


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    kernel_s: Dict[str, float]
    breakdown: dict


def reduce_events(ev: Events, chips: int = 1, top: int = 10) -> Reduced:
    win = [s for s in ev.spans if s[0] == WINDOW]
    if not win or not ev.ops:
        raise ValueError("trace holds no window span or no device ops")
    lo, hi = win[0][1], win[0][2]
    used = sorted(ev.ops)[:chips]
    busy, kernel, per_op = 0.0, {}, {}
    cls_cache: Dict[str, Optional[str]] = {}
    all_s, all_e = [], []
    for chip in used:
        rows = [(n, max(s, lo), min(e, hi)) for n, s, e in ev.ops[chip]
                if e > lo and s < hi]
        if not rows:
            continue
        st = np.array([r[1] for r in rows], np.float64)
        en = np.array([r[2] for r in rows], np.float64)
        busy += union_seconds(st, en)
        if chip == used[0]:
            all_s, all_e = st, en
        for name, s, e in rows:
            c = cls_cache.get(name, "?")
            if c == "?":
                c = cls_cache[name] = kernel_class(name)
            if c is not None:
                kernel[c] = kernel.get(c, 0.0) + (e - s) * 1e-9 / len(used)
            stem = instruction_stem(name)
            if stem not in CONTAINERS:
                per_op[stem] = per_op.get(stem, 0.0) + (e - s) * 1e-9 \
                    / len(used)
    busy_s = busy * 1e-9 / len(used)
    host = sorted((s for s in ev.spans if s[0] != WINDOW),
                  key=lambda s: s[1])
    starts = np.array([s[1] for s in host], np.float64)
    named = []
    for a, b in gaps(np.asarray(all_s), np.asarray(all_e), lo, hi):
        mid = 0.5 * (a + b)
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        name = host[i][0] if i >= 0 and host[i][2] >= mid else "other"
        named.append([name, (b - a) * 1e-9])
    named.sort(key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(busy_s, (hi - lo) * 1e-9, kernel,
                   {"device_ops": [[k, v] for k, v in ops],
                    "idle_gaps": named[:top]})


def find_xplane(tdir: str) -> str:
    found = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise ValueError(f"no .xplane.pb under {tdir}")
    return max(found, key=os.path.getmtime)


def reduce(tdir: str, chips: int = 1) -> Reduced:
    return reduce_events(load(find_xplane(tdir)), chips)
