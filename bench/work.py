"""Work the algorithm needs, counted from shapes: FLOPs and bytes.

These count what serving a token requires, whatever implements it: packed
weight bytes at each linear's channel-wise bit assignment, GEMM FLOPs at
the tokens really processed (not the padded launch width), and decode
attention over the positions each slot really attends to (not the whole
ring a kernel may read).  A later kernel that does less needless work
moves the metrics built on these; it cannot make them stale.
"""
from __future__ import annotations

import seeded as S

ACT_BYTES = 2                   # bfloat16 activations in and out of a GEMM


def linears(arch) -> list:
    """``(c_in, c_out)`` of every packed linear of one layer."""
    d = arch.hidden_size
    if arch.family == "dense":
        H, KV, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)
        ff = arch.intermediate_size
        return [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
                (d, ff), (d, ff), (ff, d)]
    d_inner = S.ssm_dims(arch)[0]
    return [(d, S.in_proj_width(arch)), (d_inner, d)]


def head(arch) -> tuple:
    return (arch.hidden_size, arch.vocab_size)


def gemm(arch, c_in: int, c_out: int, rows: int) -> tuple:
    """(FLOPs, bytes) of one packed GEMM over ``rows`` tokens."""
    flops = 2 * rows * c_in * c_out
    nbytes = S.linear_bytes(arch, c_in, c_out) + rows * (c_in + c_out) \
        * ACT_BYTES
    return flops, nbytes


def gemm_least_s(arch, rows: int, head_rows: int, peak) -> float:
    """Least time of every packed GEMM of one launch of the model over
    ``rows`` tokens, the output head over ``head_rows``: the sum over GEMMs
    of max(FLOPs / peak FLOP/s, bytes / peak bytes/s)."""
    if rows <= 0:
        return 0.0
    t = 0.0
    for ci, co in linears(arch):
        f, b = gemm(arch, ci, co, rows)
        t += max(f / peak["flops"], b / peak["bytes"])
    t *= arch.num_hidden_layers
    f, b = gemm(arch, *head(arch), head_rows)
    return t + max(f / peak["flops"], b / peak["bytes"])


def kv_token_bytes(arch) -> int:
    """Bytes of one cached position of one layer: K and V codes at
    ``kv_bits`` over every kv head, and one f32 scale per head each."""
    KV, hd = arch.num_key_value_heads, arch.head_dim
    return 2 * KV * (hd * arch.kv_bits // 8 + 4)


def decode_attn(arch, contexts: list) -> tuple:
    """(FLOPs, bytes) of one decode-attention launch of ONE layer: each live
    slot attends to its own ``context`` positions; q in f32, output bf16."""
    H, hd = arch.num_attention_heads, arch.head_dim
    n = sum(contexts)
    flops = 4 * H * hd * n
    nbytes = n * kv_token_bytes(arch) + len(contexts) * H * hd * (4 + 2)
    return flops, nbytes


def decode_attn_least_s(arch, contexts: list, peak) -> float:
    if not contexts:
        return 0.0
    f, b = decode_attn(arch, contexts)
    return arch.num_hidden_layers * max(f / peak["flops"], b / peak["bytes"])


def token_flops(arch) -> int:
    """Model FLOPs of one token through every layer and the head, less the
    context-dependent attention (``attn_flops``)."""
    f = sum(2 * ci * co for ci, co in linears(arch))
    if arch.family == "ssm":
        d_inner, H, N, P = S.ssm_dims(arch)
        # state decay and update, read-out, skip; the depthwise conv
        f += 4 * H * P * N + 2 * arch.conv_kernel * (d_inner + 2 * N)
    ci, co = head(arch)
    return arch.num_hidden_layers * f + 2 * ci * co


def attn_flops(arch, context: int) -> int:
    """Score and value FLOPs of one token attending to ``context``
    positions, over all layers (0 for attention-free models)."""
    if arch.family != "dense":
        return 0
    return arch.num_hidden_layers * 4 * arch.num_attention_heads \
        * arch.head_dim * context


def prefill_flops(arch, prompt_len: int) -> int:
    """Model FLOPs of prefilling one prompt causally (the output head runs
    on its last position only)."""
    ci, co = head(arch)
    body = token_flops(arch) - 2 * ci * co
    attn = attn_flops(arch, 1) * prompt_len * (prompt_len + 1) // 2
    return prompt_len * body + attn + 2 * ci * co
