"""Plain reference forward passes, in float32 at the highest matmul precision.

Independent of the program: architecture numbers come from the
configuration file under ``bench/configs`` and weights from ``seeded``'s
canonical form, regenerated from the seed one layer at a time.  Nothing
here caches keys or values in a lower precision, pages anything or
batches slots: a request is its prompt followed by its served tokens,
run through the whole network at once.

``act_bits`` gives the control: the same forward with every linear's input
rounded per row to signed integers of that many bits (absmax scaling), the
step below the configuration's bfloat16 activations.

Departures from the published descriptions, shared with the program and
noted in PERF.md: RoPE rotates interleaved pairs (a fixed permutation of
the q/k channels away from rotate-half, which seeded weights cannot tell
apart).  RMSNorm's epsilon and whether the head is tied come from the
configuration file, which states them as the program runs them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import seeded as W

HEAD_ROWS = 256                 # output-head rows per block


def _rms(arch, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + arch.rms_norm_eps) \
        * scale.astype(jnp.float32)


def _actq(x, bits):
    if not bits:
        return x
    qm = (1 << (bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / qm
    return jnp.round(x / s) * s


def _lin(x, groups, bits, bias=None):
    y = _actq(x, bits) @ W.dense_weight(groups).T
    return y if bias is None else y + bias.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, D), positions 0..S-1, interleaved pairs."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def _dense_layer(arch, x, base, i, bits):
    p = W.canonical_layer(arch, W.layer_key(base, i))
    B, S, _ = x.shape
    H, KV, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                 arch.head_dim)
    h = _rms(arch, x, p["ln1"])
    q = _lin(h, p["wq"], bits, p.get("bq")).reshape(B, S, H, hd)
    k = _lin(h, p["wk"], bits, p.get("bk")).reshape(B, S, KV, hd)
    v = _lin(h, p["wv"], bits, p.get("bv")).reshape(B, S, KV, hd)
    q, k = _rope(q, arch.rope_theta), _rope(k, arch.rope_theta)
    rep = H // KV
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
    x = x + _lin(o, p["wo"], bits)
    h = _rms(arch, x, p["ln2"])
    f = jax.nn.silu(_lin(h, p["w_gate"], bits)) * _lin(h, p["w_up"], bits)
    return x + _lin(f, p["w_down"], bits)


def _ssm_layer(arch, x, base, i, bits):
    """Mamba-2 block by its recurrence: h_t = exp(-A dt_t) h_{t-1}
    + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, gated RMSNorm, out_proj."""
    p = W.canonical_layer(arch, W.layer_key(base, i))
    B, S, _ = x.shape
    d_inner, H, N, P = W.ssm_dims(arch)
    zx = _lin(_rms(arch, x, p["ln"]), p["in_proj"], bits)
    z, xbc, dt = (zx[..., :d_inner], zx[..., d_inner:2 * d_inner + 2 * N],
                  zx[..., 2 * d_inner + 2 * N:])
    K = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + S] * p["conv_w"][j].astype(jnp.float32)
               for j in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs = xbc[..., :d_inner].reshape(B, S, H, P)
    Bm, Cm = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = jnp.exp(jnp.asarray(W.a_log(arch)))

    def step(h, t):
        xt, dtt, bt, ct = t
        h = h * jnp.exp(-A * dtt)[:, :, None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", xt, bt, dtt)
        return h, jnp.einsum("bhpn,bn->bhp", h, ct)

    seq = tuple(jnp.swapaxes(a, 0, 1) for a in (xs, dt, Bm, Cm))
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32), seq)
    y = jnp.swapaxes(y, 0, 1) + xs * p["D"][None, None, :, None]
    y = _rms(arch, y.reshape(B, S, d_inner) * jax.nn.silu(z),
             p["norm"])
    return x + _lin(y, p["out_proj"], bits)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(arch, x, base, i, bits):
    fn = _dense_layer if arch.family == "dense" else _ssm_layer
    with jax.default_matmul_precision("highest"):
        return fn(arch, x, base, i, bits)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(arch, top, tokens):
    return W.canonical_top(arch, top)["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(arch, top, x, tok, bits):
    """Final norm and output head over rows ``x (R, d)``: each row's best
    logit, its argmax, and the logit of ``tok (R,)``."""
    t = W.canonical_top(arch, top)
    with jax.default_matmul_precision("highest"):
        lg = _lin(_rms(arch, x, t["ln_f"]), t["lm_head"], bits)
    return (jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0])


def hidden(arch, seed: int, tokens: np.ndarray, act_bits: int = 0):
    """Last hidden states ``(B, S, d)`` of right-padded ``tokens (B, S)``
    (padding sits after every real token, so causal rows never see it)."""
    top, base = W.keys(seed)
    x = _embed(arch, top, jnp.asarray(tokens))
    for i in range(arch.num_hidden_layers):
        x = _layer(arch, x, base, i, act_bits)
    return x


def head(arch, seed: int, rows, tok, act_bits: int = 0):
    """``_head`` over rows ``(R, d)`` in blocks of one compiled shape;
    numpy (best, argmax, logit of tok)."""
    top, _ = W.keys(seed)
    outs = []
    for r0 in range(0, rows.shape[0], HEAD_ROWS):
        blk, t = rows[r0:r0 + HEAD_ROWS], tok[r0:r0 + HEAD_ROWS]
        n = blk.shape[0]
        blk = jnp.pad(blk, ((0, HEAD_ROWS - n), (0, 0)))
        t = jnp.pad(jnp.asarray(t, jnp.int32), (0, HEAD_ROWS - n))
        outs.append([np.asarray(a)[:n] for a in
                     _head(arch, top, blk, t, act_bits)])
    return tuple(np.concatenate(c) for c in zip(*outs))


def _rows(arch, seed, samples, act_bits, batch):
    """Hidden rows that predict each served token, request by request in
    ``batch``-request blocks padded to a multiple of 128 positions."""
    rows = []
    for b0 in range(0, len(samples), batch):
        blk = samples[b0:b0 + batch]
        seqs = [np.concatenate([p, s[:-1]]) for p, s in blk]
        S = -(-max(len(q) for q in seqs) // 128) * 128
        toks = np.zeros((batch, S), np.int32)
        for r, q in enumerate(seqs):
            toks[r, :len(q)] = q
        h = hidden(arch, seed, toks, act_bits)
        for r, (p, s) in enumerate(blk):
            rows.append(h[r, len(p) - 1:len(p) - 1 + len(s)])
    return jnp.concatenate(rows, axis=0)


def gaps(arch, seed: int, samples, control_bits: int = 0,
         batch: int = 4) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over ``samples = [(prompt, served), ...]``.

    With ``control_bits`` the served tokens are replaced, position by
    position, by the tokens the control (activations at ``control_bits``)
    puts first, and their gap under the reference is read instead."""
    served = np.concatenate([s for _, s in samples]).astype(np.int32)
    rows = _rows(arch, seed, samples, 0, batch)
    tok = served
    if control_bits:
        crow = _rows(arch, seed, samples, control_bits, batch)
        _, tok, _ = head(arch, seed, crow, served, control_bits)
    best, top, at = head(arch, seed, rows, tok)
    gap = best - at
    return {"widest_gap": float(gap.max()), "tokens": int(len(tok)),
            "mean_gap": float(gap.mean()),
            "p99_gap": float(np.percentile(gap, 99)),
            "argmax_agree": float((top == tok).mean())}
