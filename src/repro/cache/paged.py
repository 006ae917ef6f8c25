"""Device-side page index plumbing: fixed-shape gather/scatter over pools.

A paged ring leaf stores its sequence axis as ``(num_pages, page_size)``
physical pages with a stacking axis in front: ``pool (X, num_pages, *mid,
page_size, feat)`` where ``X`` is the layer/group stack and ``*mid`` is
e.g. the KV-head axis.  A slot's logical ring of ``max_len = P * T``
positions is the concatenation of the ``P`` pages its ``(B, P)`` int32
page-table row points at; entry 0 (the NULL page) is reserved, never
written, and always zero — gathering through an unmapped entry reads the
dense ring's empty-slot zeros.

Every helper here is shape-static in everything but the index *values*:
the gathered view is exactly the dense ``(B, *mid, max_len, feat)`` ring
(this is what makes the paged engine bit-identical to the dense slot pool
— masked positions contribute exact-0.0 softmax weight either way), and
the scatters use ``mode="drop"`` with out-of-bounds sentinels so dead rows
and shared (read-only) pages skip their writes with no shape change.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("kv_gather")
def gather_pages(pool: jnp.ndarray, pages: jnp.ndarray) -> jnp.ndarray:
    """Materialize per-slot rings: pool ``(NP, *mid, T, F)`` gathered by
    ``pages (B, P)`` -> ``(B, *mid, P*T, F)`` — the dense ring layout the
    decode attention math already consumes."""
    g = pool[pages]                         # (B, P, *mid, T, F)
    nm = g.ndim - 4                         # number of *mid axes
    perm = (0,) + tuple(range(2, 2 + nm)) + (1, 2 + nm, 3 + nm)
    g = jnp.transpose(g, perm)              # (B, *mid, P, T, F)
    sh = g.shape
    return g.reshape(sh[:-3] + (sh[-3] * sh[-2], sh[-1]))


def write_coords(pos: jnp.ndarray, live: Optional[jnp.ndarray],
                 pages: jnp.ndarray, page_size: int, num_pages: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-slot (physical page, in-page offset) for a decode write.

    ``pos (B,)`` positions, ``pages (B, P)`` table.  Rows that must not
    write — dead (``live=False``), position past the table, or mapped to
    the NULL page (the scheduler suppresses a write by leaving the entry
    unmapped or masking ``live``) — get page index ``num_pages``, out of
    bounds so the ``mode="drop"`` scatter skips them.  The NULL page is
    thereby never written and stays all-zero.

    ``pos`` may also be ``(B, W)`` — the multi-token verify launch of
    speculative decoding writes ``W`` consecutive entries per slot in one
    scatter; the returned coordinate arrays are then ``(B, W)`` with the
    same drop rules applied elementwise (``live`` still masks whole
    slots).  A write that lands past the slot's mapped pages is dropped,
    never unwound — the cache-rewind contract: rejected draft positions
    stay masked (``<= pos``) until later writes overwrite them.
    """
    pos = pos.astype(jnp.int32)
    P = pages.shape[1]
    S = P * page_size
    vec = pos.ndim == 1
    posw = pos[:, None] if vec else pos                    # (B, W)
    wpos = posw if live is None else jnp.where(live[:, None], posw, S)
    pidx = jnp.clip(wpos // page_size, 0, P - 1)
    phys = jnp.take_along_axis(pages, pidx, axis=1)        # (B, W)
    drop = (wpos >= S) | (phys == 0)
    phys = jnp.where(drop, num_pages, phys)
    off = wpos % page_size
    return (phys[:, 0], off[:, 0]) if vec else (phys, off)


def scatter_prefill(pool: jnp.ndarray, pf: jnp.ndarray,
                    wp_flat: jnp.ndarray) -> jnp.ndarray:
    """Scatter an admission's prefill cache into the page pool.

    ``pool (X, NP, *mid, T, F)``; ``pf (X, B, *mid, Sp, F)`` with the
    prefill width ``Sp = n_pp * T``; ``wp_flat (B * n_pp,)`` int32 maps
    slot ``b``'s prompt page ``j`` (flattened ``b * n_pp + j``) to its
    physical page — or to ``NP`` (out of bounds, dropped) for pages that
    must not be written: slots not being admitted, the junk tail past a
    short prompt, and prefix-shared pages (read-only, already holding the
    identical bits from the prefill that first produced them).
    """
    X, B = pf.shape[0], pf.shape[1]
    T, F = pool.shape[-2], pool.shape[-1]
    mid = pf.shape[2:-2]
    n_pp = pf.shape[-2] // T
    nm = len(mid)
    x = pf.reshape((X, B) + mid + (n_pp, T, F))
    perm = (0, 1, 2 + nm) + tuple(range(2, 2 + nm)) + (3 + nm, 4 + nm)
    x = jnp.transpose(x, perm).reshape((X, B * n_pp) + mid + (T, F))
    return pool.at[:, wp_flat].set(x.astype(pool.dtype), mode="drop")
