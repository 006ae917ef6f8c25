"""Mamba2 (state-space duality, arXiv:2405.21060) — chunked SSD in pure JAX.

The block:  x -> in_proj -> [z | xBC | dt] ; causal conv1d over xBC ; split
x/B/C ; SSD recurrence over heads with scalar-per-head decay A ; gated (silu z)
output ; out_proj.

SSD is computed with the **chunked** algorithm: the sequence splits into
chunks of length Q; within a chunk the recurrence is a (masked, decay-
weighted) attention-like quadratic form; across chunks a lax.scan carries the
(H, P, N) state.  Cost is O(S·Q) instead of O(S²) — this is the sub-quadratic
path that makes the long_500k (524288-token) dry-run cell feasible, and the
O(1)-state decode step.

Quantization: in_proj/out_proj are channel-wise searchable (qlinear); the
recurrence itself runs bf16/f32 (state recurrences are precision-sensitive —
the same reason the paper keeps norms float; DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import layers as L

CONV_K = 4  # mamba2 depthwise conv kernel size


def dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_state, cfg.ssm_head_dim


def init_mamba2(key, cfg, dtype) -> tuple[dict, dict]:
    d = cfg.d_model
    d_inner, H, N, P = dims(cfg)
    conv_dim = d_inner + 2 * N          # x + B + C  (n_groups=1)
    ks = jax.random.split(key, 4)
    params = {
        "in_proj": L.linear_init(ks[0], d, 2 * d_inner + 2 * N + H, dtype),
        "out_proj": L.linear_init(ks[1], d_inner, d, dtype),
        "conv_w": (jax.random.normal(ks[2], (CONV_K, conv_dim)) /
                   math.sqrt(CONV_K)).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": L.norm_init(d_inner, "rmsnorm", dtype),
    }
    nas = {
        "in_proj": L.nas_init(ks[0], 2 * d_inner + 2 * N + H, cfg.quant),
        "out_proj": L.nas_init(ks[1], d, cfg.quant),
    }
    return params, nas


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                 ) -> jnp.ndarray:
    """Depthwise causal conv1d: xbc (B, S, C), w (K, C)."""
    B, S, C = xbc.shape
    pad = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc)
    for i in range(CONV_K):
        out = out + pad[:, i:i + S, :] * w[i]
    return jax.nn.silu(out + b)


def ssd_chunked(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                Bm: jnp.ndarray, Cm: jnp.ndarray, chunk: int,
                h0: Optional[jnp.ndarray] = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.

    xh (B,S,H,P) inputs per head; dt (B,S,H) softplus'd steps; A (H,) decay
    rates (positive); Bm/Cm (B,S,N) shared across heads (n_groups=1).
    Returns (y (B,S,H,P), final state (B,H,P,N)).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))

    # reshape to (nc, B, Q, ...) for scan over chunks
    def to_chunks(t):
        return t.reshape(Bsz, nc, chunk, *t.shape[2:]).swapaxes(0, 1)
    xc, dtc, Bc, Cc = map(to_chunks, (xh, dt, Bm, Cm))
    # scan xs lose their sharding without constraints (dist/sharding.py):
    # keep batch on data and the head dim on model through the chunk scan
    xc = constrain(xc, None, "D", None, "M", None)
    dtc = constrain(dtc, None, "D", None, "M")
    Bc = constrain(Bc, None, "D", None, None)
    Cc = constrain(Cc, None, "D", None, None)

    A = -A  # decay: dA = -A*dt <= 0

    def body(h, xs):
        xq, dtq, Bq, Cq = xs          # (B,Q,H,P), (B,Q,H), (B,Q,N), (B,Q,N)
        xq = constrain(xq, "D", None, "M", None)
        h = constrain(h, "D", "M", None, None)
        dA = dtq * A                  # (B,Q,H)  (<=0)
        cum = jnp.cumsum(dA, axis=1)  # inclusive cumsum over chunk
        # intra-chunk: Lmat[t,s] = exp(cum[t]-cum[s]) for s<=t  (B,H,Q,Q)
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # (B,Qt,Qs,H)
        mask = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
        Lmat = jnp.where(mask[None, :, :, None], jnp.exp(diff), 0.0)
        CB = jnp.einsum("btn,bsn->bts", Cq, Bq)              # (B,Qt,Qs)
        W = CB[:, :, :, None] * Lmat                         # (B,Qt,Qs,H)
        xdt = xq * dtq[..., None]                            # (B,Q,H,P)
        y_intra = jnp.einsum("btsh,bshp->bthp", W, xdt)
        # inter-chunk: contribution of carried state
        decay_in = jnp.exp(cum)                              # (B,Q,H)
        y_inter = jnp.einsum("btn,bhpn,bth->bthp", Cq, h, decay_in)
        # state update: h' = exp(cum[-1]) h + sum_s exp(cum[-1]-cum[s]) B_s xdt_s
        tail = jnp.exp(cum[:, -1:, :] - cum)                 # (B,Q,H)
        h_new = h * jnp.exp(cum[:, -1, :])[:, :, None, None]  # (B,H,P,N)
        h_new = h_new + jnp.einsum("bsn,bshp,bsh->bhpn", Bq, xdt, tail)
        return h_new, y_intra + y_inter

    if h0 is None:
        h0 = constrain(jnp.zeros((Bsz, H, P, N), xh.dtype),
                       "D", "M", None, None)
    hT, yc = jax.lax.scan(body, h0, (xc, dtc, Bc, Cc))
    y = yc.swapaxes(0, 1).reshape(Bsz, nc * chunk, H, P)[:, :S]
    return y, hT


def mamba2_forward(p: dict, nas: Optional[dict], policy, cfg,
                   x: jnp.ndarray) -> jnp.ndarray:
    """Full-sequence Mamba2 block. x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    d_inner, H, N, P = dims(cfg)
    cd = cfg.cdtype
    getn = (lambda n: nas[n]) if nas is not None else (lambda n: None)
    zxbcdt = L.qlinear(x, p["in_proj"], getn("in_proj"), policy, cfg.quant,
                       compute_dtype=cd)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + d_inner + 2 * N]
    dt_raw = zxbcdt[..., -H:]
    xbc = _causal_conv(xbc, p["conv_w"].astype(cd), p["conv_b"].astype(cd))
    xs = xbc[..., :d_inner].reshape(B, S, H, P)
    Bm = xbc[..., d_inner:d_inner + N]
    Cm = xbc[..., d_inner + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = jnp.exp(p["A_log"])
    y, _ = ssd_chunked(xs.astype(jnp.float32), dt, A,
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                       cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(cd)
    y = L.rmsnorm(y * jax.nn.silu(z.astype(cd)), p["norm"])
    return L.qlinear(y, p["out_proj"], getn("out_proj"), policy, cfg.quant,
                     compute_dtype=cd)


# ---------------------------------------------------------------------------
# Decode path: O(1) recurrent step with (state, conv ring buffer) cache
# ---------------------------------------------------------------------------

def init_ssm_cache(cfg, batch: int) -> dict:
    d_inner, H, N, P = dims(cfg)
    conv_dim = d_inner + 2 * N
    return {
        "h": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, CONV_K - 1, conv_dim), jnp.bfloat16),
    }


@jax.named_scope("ssm")
def mamba2_decode(p: dict, cfg, x: jnp.ndarray, cache: dict, dq_linear,
                  live: Optional[jnp.ndarray] = None
                  ) -> tuple[jnp.ndarray, dict]:
    """Single-token recurrent step. x: (B, 1, d).

    ``live``: optional (B,) bool slot mask — rows with ``live=False`` keep
    their cached recurrent state and conv ring untouched (the SSM analogue
    of the attention caches' dropped ring write), so freed slots in a
    fixed-width serving batch cannot drift while they wait for admission.
    """
    B = x.shape[0]
    d_inner, H, N, P = dims(cfg)
    cd = cfg.cdtype
    zxbcdt = dq_linear(x, p["in_proj"])[:, 0]            # (B, 2di+2N+H)
    z = zxbcdt[..., :d_inner]
    xbc_new = zxbcdt[..., d_inner:d_inner + d_inner + 2 * N]
    dt_raw = zxbcdt[..., -H:]
    # conv ring buffer
    window = jnp.concatenate([cache["conv"].astype(cd),
                              xbc_new[:, None].astype(cd)], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(cd))
    xbc = jax.nn.silu(conv_out + p["conv_b"].astype(cd))
    new_conv = window[:, 1:].astype(jnp.bfloat16)

    xs = xbc[..., :d_inner].reshape(B, H, P).astype(jnp.float32)
    Bm = xbc[..., d_inner:d_inner + N].astype(jnp.float32)
    Cm = xbc[..., d_inner + N:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = jnp.exp(p["A_log"])
    decay = jnp.exp(-A * dt)                              # (B,H)
    h = cache["h"] * decay[:, :, None, None] + jnp.einsum(
        "bn,bhp,bh->bhpn", Bm, xs, dt)
    y = jnp.einsum("bn,bhpn->bhp", Cm, h)
    y = y + xs * p["D"][None, :, None]
    y = y.reshape(B, 1, d_inner).astype(cd)
    y = L.rmsnorm(y * jax.nn.silu(z[:, None].astype(cd)), p["norm"])
    out = dq_linear(y, p["out_proj"])
    if live is not None:
        h = jnp.where(live[:, None, None, None], h, cache["h"])
        new_conv = jnp.where(live[:, None, None], new_conv, cache["conv"])
    return out, {"h": h, "conv": new_conv}
