"""Token sampling for the serving surfaces.

One helper shared by the request-level :class:`~repro.api.scheduler.
ServingEngine` and the lockstep oracle loops over ``engine.serving_jits``
(the removed ``ServingSession`` hard-coded ``argmax`` inline, twice).
The sampling *kind*
is static — jitted serving steps specialize per :class:`SamplingParams`
exactly like they specialize per backend — so greedy decoding stays a
pure ``argmax`` with no RNG plumbed through the hot path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

KINDS = ("greedy", "temperature", "top_k")


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static sampling configuration (hashable — usable as a jit-cache key).

    * ``greedy`` — deterministic ``argmax`` (the default; no key needed);
    * ``temperature`` — softmax sampling at ``temperature``;
    * ``top_k`` — restrict to the ``top_k`` highest logits, then
      temperature-sample within them (``top_k=1`` degenerates to greedy
      for every key — pinned by tests/test_continuous_batching.py).
    """
    kind: str = "greedy"
    temperature: float = 1.0
    top_k: int = 0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown sampling kind {self.kind!r}; "
                             f"one of {KINDS}")
        if self.kind == "top_k" and self.top_k < 1:
            raise ValueError("top_k sampling needs top_k >= 1")
        if self.temperature <= 0:
            raise ValueError("temperature must be > 0")
        # inapplicable knobs raise instead of being silently ignored: a
        # trace configured with kind="temperature", top_k=5 used to sample
        # the FULL vocab and look like a model bug downstream
        if self.kind != "top_k" and self.top_k != 0:
            raise ValueError(
                f"top_k={self.top_k} is inapplicable to kind="
                f"{self.kind!r} and would be silently ignored; use "
                "kind='top_k' (or leave top_k=0)")
        if self.kind == "greedy" and self.temperature != 1.0:
            raise ValueError(
                f"temperature={self.temperature} is inapplicable to "
                "greedy sampling (argmax is temperature-invariant); use "
                "kind='temperature' (or leave temperature=1.0)")


GREEDY = SamplingParams()


@jax.named_scope("sample")
def sample(logits: jnp.ndarray, params: SamplingParams = GREEDY,
           key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Sample token ids from ``logits (..., V)`` -> int32 ``(...)``.

    Leading axes are preserved (serving passes ``(B, 1, V)`` and gets the
    ``(B, 1)`` next-token batch back).  ``key`` is required for the
    stochastic kinds and ignored by ``greedy``.
    """
    if params.kind == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError(f"sampling kind {params.kind!r} needs a PRNG key")
    lg = logits.astype(jnp.float32) / params.temperature
    if params.kind == "top_k":
        # clamp: top_k is a request knob, not a vocab fact — asking for more
        # candidates than the vocab axis holds means "no restriction", while
        # the unclamped lax.top_k call is a crash inside jit.  The strict
        # `lg < kth` mask keeps ALL logits tied with the kth one.
        k = min(params.top_k, lg.shape[-1])
        kth = jax.lax.top_k(lg, k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def _dist(logits: jnp.ndarray, params: SamplingParams) -> jnp.ndarray:
    """The probability distribution ``sample`` draws from: filtered softmax
    over ``logits (..., V)`` -> f32 probs ``(..., V)``.  Shared by the
    stochastic speculative acceptance so the draft proposal q and verifier
    target p see exactly the temperature/top-k filtering the engine's
    sampling kind applies."""
    lg = logits.astype(jnp.float32) / params.temperature
    if params.kind == "top_k":
        k = min(params.top_k, lg.shape[-1])
        kth = jax.lax.top_k(lg, k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    return jax.nn.softmax(lg, axis=-1)


@jax.named_scope("sample")
def speculative_accept(draft_tokens: jnp.ndarray, draft_logits: jnp.ndarray,
                       verify_logits: jnp.ndarray,
                       params: SamplingParams = GREEDY,
                       key: Optional[jax.Array] = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rejection-sampling acceptance for draft/verify speculative decoding.

    ``draft_tokens (B, k)`` — the k tokens the draft proposed;
    ``draft_logits (B, k, V)`` — the draft logits each was sampled from;
    ``verify_logits (B, k+1, V)`` — the verifier's logits at the k+1
    positions of the verify launch (inputs ``[t0, d1..dk]``, so row ``j``
    is the verifier's distribution for the token AFTER accepting
    ``d1..dj``).

    Returns ``(accepted (B,), out_tokens (B, k+1))``: row ``b`` emits
    ``out_tokens[b, :accepted[b] + 1]`` — the accepted draft prefix plus
    one final token from the verifier (the corrected token at the first
    rejection, or the free bonus token when all k drafts survive).

    * ``greedy`` degenerates to **exact prefix match**: a draft token is
      accepted iff it equals the verifier argmax at its position, and every
      emitted token IS a verifier argmax — the speculative engine is
      bit-identical to the non-speculative one (the parity anchor, and it
      holds for ANY draft, however aggressive its bit-width).
    * stochastic kinds run standard rejection sampling on the filtered
      distributions (:func:`_dist`): accept ``d_j`` with prob
      ``min(1, p(d_j)/q(d_j))``; on rejection resample from the residual
      ``max(p - q, 0)`` (normalized); on full acceptance the bonus token
      samples ``p`` directly — output tokens are distributed EXACTLY as
      verifier-only sampling (Leviathan et al., arXiv:2211.17192 Thm. 1;
      the zero-padded q row makes the bonus the ``m == k`` case of the
      same residual formula).
    """
    B, k = draft_tokens.shape
    draft_tokens = draft_tokens.astype(jnp.int32)
    if params.kind == "greedy":
        vt = jnp.argmax(verify_logits, axis=-1).astype(jnp.int32)  # (B, k+1)
        match = (draft_tokens == vt[:, :k]).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)     # (B,)
        return accepted, vt
    if key is None:
        raise ValueError(f"sampling kind {params.kind!r} needs a PRNG key")
    p = _dist(verify_logits, params)                   # (B, k+1, V)
    q = _dist(draft_logits, params)                    # (B, k,   V)
    k_u, k_r = jax.random.split(key)
    pd = jnp.take_along_axis(p[:, :k], draft_tokens[..., None],
                             axis=-1)[..., 0]          # (B, k)
    qd = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    u = jax.random.uniform(k_u, (B, k))
    acc = (u < jnp.minimum(1.0, pd / jnp.maximum(qd, 1e-30))).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)           # (B,)
    # residual at the first rejected slot; q zero-padded so the all-accept
    # bonus is just the m == k row of the same formula (residual = p_k)
    q_pad = jnp.concatenate([q, jnp.zeros_like(p[:, :1])], axis=1)
    pm = jnp.take_along_axis(p, accepted[:, None, None], axis=1)[:, 0]
    qm = jnp.take_along_axis(q_pad, accepted[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(pm - qm, 0.0)
    resid = resid / jnp.maximum(resid.sum(-1, keepdims=True), 1e-30)
    corr = jax.random.categorical(
        k_r, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1).astype(jnp.int32)
    out = jnp.concatenate(
        [draft_tokens, jnp.zeros((B, 1), jnp.int32)], axis=1)
    out = out.at[jnp.arange(B), accepted].set(corr)
    return accepted, out
