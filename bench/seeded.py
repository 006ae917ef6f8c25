"""Seeded weights in canonical form, from the configuration file alone.

Every number comes from integer draws of ``jax.random`` (codes, and small
integers times powers of two), so the weights of layer ``i`` are the same
bits whether they are made for the program (all layers in one jitted call,
``loader.program_params``) or for the reference (one layer at a time).
Nothing here imports the program.

A linear is its channel groups ``[(bits, q (n, c_in) int8, step (n,) f32),
...]`` in ascending bits; its effective weight is ``concat(q * step)``.
Group sizes follow the configuration's static deploy assignment: each
fraction of ``c_out`` rounded to ``align`` channels, the highest precision
taking the rest.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np


class Arch(dict):
    """A configuration file's record, hashable (a jit static argument) and
    read by attribute."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError:
            raise AttributeError(k) from None


def served_arch(record: dict) -> Arch:
    """A configuration file's record as it is served: the vocabulary
    padded to the published ``pad_vocab_size_multiple``, as the published
    model pads its embedding and output head."""
    m = int(record.get("pad_vocab_size_multiple", 1))
    return Arch({**record, "vocab_size": -(-int(record["vocab_size"]) // m)
                 * m})


def _qmax(b: int) -> int:
    return (1 << (b - 1)) - 1


def _ints(key, shape, lo, hi):
    return jax.random.randint(key, shape, lo, hi, jnp.int32)


def group_sizes(arch, c_out: int) -> list:
    """``[(bits, n), ...]`` of a linear with ``c_out`` channels."""
    bits = sorted(arch.weight_bits)
    align = min(arch.deploy_align, c_out)
    sizes, used = [], 0
    for frac, b in list(zip(arch.deploy_fractions, bits))[:-1]:
        n = int(round(frac * c_out / align) * align)
        n = max(0, min(n, c_out - used))
        sizes.append((b, n))
        used += n
    sizes.append((bits[-1], c_out - used))
    return [(b, n) for b, n in sizes if n]


def canonical_linear(key, arch, c_in: int, c_out: int) -> list:
    """Uniform codes in ``[-qmax, qmax]`` and per-channel steps that give
    each output about unit variance for unit-variance inputs."""
    out = []
    for b, n in group_sizes(arch, c_out):
        kq, ks = jax.random.split(jax.random.fold_in(key, b))
        qm = _qmax(b)
        q = _ints(kq, (n, c_in), -qm, qm + 1).astype(jnp.int8)
        std = math.sqrt(((2 * qm + 1) ** 2 - 1) / 12.0)
        base = np.float32(1.0 / (std * math.sqrt(c_in)))
        step = (1.0 + _ints(ks, (n,), 0, 17).astype(jnp.float32) / 32.0) * base
        out.append((b, q, step))
    return out


def dense_weight(groups, dtype=jnp.float32) -> jnp.ndarray:
    """``(c_out, c_in)`` effective weight of a canonical linear."""
    return jnp.concatenate([q.astype(jnp.float32) * s[:, None]
                            for _, q, s in groups], axis=0).astype(dtype)


def _scale_vec(key, n):
    """Norm scales near 1: ``1 + k / 64``, exact in bfloat16."""
    return (1.0 + _ints(key, (n,), -8, 9).astype(jnp.float32) / 64.0
            ).astype(jnp.bfloat16)


def _small(key, shape, shift):
    """Integers in [-127, 127] times ``2**-shift``, exact in bfloat16."""
    return (_ints(key, shape, -127, 128).astype(jnp.float32)
            * (2.0 ** -shift)).astype(jnp.bfloat16)


def ssm_dims(arch):
    """(d_inner, heads, state, head dim) of a Mamba-2 block."""
    d_inner = arch.expand * arch.hidden_size
    return d_inner, d_inner // arch.ssm_head_dim, arch.state_size, \
        arch.ssm_head_dim


def in_proj_width(arch) -> int:
    d_inner, H, N, _ = ssm_dims(arch)
    return 2 * d_inner + 2 * N + H


def canonical_layer(arch, key) -> dict:
    """One layer's weights in canonical form."""
    d = arch.hidden_size
    ks = jax.random.split(key, 12)
    if arch.family == "dense":
        H, KV, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)
        ff = arch.intermediate_size
        p = {"wq": canonical_linear(ks[0], arch, d, H * hd),
             "wk": canonical_linear(ks[1], arch, d, KV * hd),
             "wv": canonical_linear(ks[2], arch, d, KV * hd),
             "wo": canonical_linear(ks[3], arch, H * hd, d),
             "w_gate": canonical_linear(ks[4], arch, d, ff),
             "w_up": canonical_linear(ks[5], arch, d, ff),
             "w_down": canonical_linear(ks[6], arch, ff, d),
             "ln1": _scale_vec(ks[7], d), "ln2": _scale_vec(ks[8], d)}
        if arch.qkv_bias:
            p["bq"] = _small(ks[9], (H * hd,), 10)
            p["bk"] = _small(ks[10], (KV * hd,), 10)
            p["bv"] = _small(ks[11], (KV * hd,), 10)
        return p
    if arch.family == "ssm":
        d_inner, H, N, _ = ssm_dims(arch)
        conv_dim = d_inner + 2 * N
        return {"in_proj": canonical_linear(ks[0], arch, d,
                                            in_proj_width(arch)),
                "out_proj": canonical_linear(ks[1], arch, d_inner, d),
                "conv_w": _small(ks[2], (arch.conv_kernel, conv_dim), 7),
                "conv_b": _small(ks[3], (conv_dim,), 9),
                "D": 1.0 + _ints(ks[4], (H,), -16, 17).astype(jnp.float32)
                / 64.0,
                "dt_bias": _ints(ks[5], (H,), -64, 1).astype(jnp.float32)
                / 32.0,
                "norm": _scale_vec(ks[6], d_inner),
                "ln": _scale_vec(ks[7], d)}
    raise ValueError(f"no seeded weights for family {arch.family!r}")


def a_log(arch) -> np.ndarray:
    """Mamba-2's per-head decay rates, ``log(linspace(1, 16))``, made on
    the host so that both sides get the same bits."""
    return np.log(np.linspace(1.0, 16.0, ssm_dims(arch)[1])).astype(
        np.float32)


def canonical_top(arch, key) -> dict:
    """Embedding, final norm and output head."""
    ks = jax.random.split(key, 3)
    return {"embed": _small(ks[0], (arch.vocab_size, arch.hidden_size), 12),
            "ln_f": _scale_vec(ks[1], arch.hidden_size),
            "lm_head": canonical_linear(ks[2], arch, arch.hidden_size,
                                        arch.vocab_size)}


def keys(seed: int):
    """(top key, per-layer base key) of a seed of any size up to 64 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(0)
    for half in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        k = jax.random.fold_in(k, np.uint32(half))
    top, layers = jax.random.split(k)
    return top, layers


def layer_key(base, i):
    return jax.random.fold_in(base, i)


def linear_bytes(arch, c_in: int, c_out: int) -> int:
    """Bytes a GEMM must read for one linear at its channel-wise bit
    assignment: the codes at their bits plus one f32 step per channel."""
    return sum(n * c_in * b // 8 + 4 * n for b, n in group_sizes(arch, c_out))
