"""Hand seeded weights to the program in its served type.

The canonical weights of ``seeded`` are packed by the program's own
functions (``quantizers.pack_int``, ``quant_matmul.pack_fused_tiles``) into
the ``QTensor`` tree that ``models/serving`` serves: per-group packed codes,
plus the fused single-launch layout where the contraction fits one K step,
as the program's own deployment builds them (``serving.init_deployed_linear``
draws random weights and so cannot take given codes).  ``check_layout``
holds the tree to the structure, static data and leaf shapes of
``serving.init_deployed_model`` in every run, so a change of the program's
deployed layout stops the benchmark instead of going unmeasured.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import seeded as S
from repro.api.qtensor import QTensor, _auto_tile_n
from repro.core import quantizers as qz
from repro.kernels import quant_matmul as qmk


def to_qtensor(cfg, groups, c_in: int, c_out: int) -> QTensor:
    Kp = -(-c_in // qmk.FUSED_K_ALIGN) * qmk.FUSED_K_ALIGN
    tile_n = min(_auto_tile_n(c_out), cfg.deploy.align) \
        if Kp <= qmk.K_SINGLE_STEP_MAX else None
    packed, steps, bits = [], [], []
    fused_p, fused_s, tile_bits, tcol = [], [], [], []
    dep = 0
    for b, q, s in groups:
        n = q.shape[0]
        f = qz.pack_factor(b)
        kpad = Kp if tile_n is not None else -(-c_in // f) * f
        qk = jnp.pad(q, ((0, 0), (0, kpad - c_in)))
        packed.append(qz.pack_int(qk, b))
        steps.append(s)
        bits.append(b)
        if tile_n is not None:
            pad = (-n) % tile_n
            fused_p.append(qmk.pack_fused_tiles(
                jnp.pad(qk, ((0, pad), (0, 0))), b, tile_n))
            fused_s.append(jnp.pad(s, (0, pad)))
            tile_bits += [b] * ((n + pad) // tile_n)
            tcol += list(range(dep, dep + n)) + [-1] * pad
        dep += n
    fused = {}
    if tile_n is not None:
        tcol = np.asarray(tcol)
        perm = None
        if not ((tcol[:c_out] == np.arange(c_out)).all()
                and (tcol[c_out:] < 0).all()):
            cols = np.nonzero(tcol >= 0)[0].astype(np.int32)
            gather = np.zeros(c_out, np.int32)
            gather[tcol[cols]] = cols
            perm = jnp.asarray(gather)
        fused = dict(fused_packed=jnp.concatenate(fused_p, axis=-1),
                     fused_scales=jnp.concatenate(fused_s, axis=-1),
                     fused_perm=perm, tile_bits=tuple(tile_bits),
                     tile_n=tile_n)
    return QTensor(tuple(packed), tuple(steps), None, tuple(bits), c_out,
                   c_in, act_bits=cfg.deploy.act_bits, restore_order=False,
                   **fused)


def _lin(cfg, groups, c_in, c_out, bias=None):
    out = {"w": to_qtensor(cfg, groups, c_in, c_out)}
    if bias is not None:
        out["bias"] = bias
    return out


def _layer(arch, cfg, c: dict) -> dict:
    d = arch.hidden_size
    if arch.family == "dense":
        H, KV, hd = (arch.num_attention_heads, arch.num_key_value_heads,
                     arch.head_dim)
        ff = arch.intermediate_size
        return {"attn": {"wq": _lin(cfg, c["wq"], d, H * hd, c.get("bq")),
                         "wk": _lin(cfg, c["wk"], d, KV * hd, c.get("bk")),
                         "wv": _lin(cfg, c["wv"], d, KV * hd, c.get("bv")),
                         "wo": _lin(cfg, c["wo"], H * hd, d)},
                "ffn": {"w_gate": _lin(cfg, c["w_gate"], d, ff),
                        "w_up": _lin(cfg, c["w_up"], d, ff),
                        "w_down": _lin(cfg, c["w_down"], ff, d)},
                "ln1": {"scale": c["ln1"]}, "ln2": {"scale": c["ln2"]}}
    d_inner = S.ssm_dims(arch)[0]
    return {"in_proj": _lin(cfg, c["in_proj"], d, S.in_proj_width(arch)),
            "out_proj": _lin(cfg, c["out_proj"], d_inner, d),
            "conv_w": c["conv_w"], "conv_b": c["conv_b"],
            "A_log": jnp.asarray(S.a_log(arch)), "D": c["D"],
            "dt_bias": c["dt_bias"],
            "norm": {"scale": c["norm"]}, "ln": {"scale": c["ln"]}}


def program_params(arch, cfg, seed: int) -> dict:
    """The whole served parameter tree, made on the device in one jitted
    call.  Layers are made one after another (``lax.map``), so the peak is
    the packed model and one layer's codes."""
    def make(top_key, base):
        blocks = jax.lax.map(
            lambda i: _layer(arch, cfg, S.canonical_layer(
                arch, S.layer_key(base, i))),
            jnp.arange(arch.num_hidden_layers))
        top = S.canonical_top(arch, top_key)
        return {"embed": top["embed"], "blocks": blocks,
                "ln_f": {"scale": top["ln_f"]},
                "lm_head": _lin(cfg, top["lm_head"], arch.hidden_size,
                                arch.vocab_size)}
    return jax.jit(make)(*S.keys(seed))


def check_layout(cfg, params) -> None:
    """Raise unless ``params`` has the tree structure (with every
    ``QTensor``'s static data: bits, tile schedule, widths) and the leaf
    shapes and types of the program's own ``init_deployed_model``."""
    from repro.models import serving
    want = jax.eval_shape(lambda k: serving.init_deployed_model(cfg, k),
                          jax.random.PRNGKey(0))
    ws, gs = jax.tree_util.tree_structure(want), \
        jax.tree_util.tree_structure(params)
    if ws != gs:
        raise ValueError(f"seeded tree differs from the program's deployed "
                         f"layout:\n{gs}\nagainst\n{ws}")
    shape = lambda x: (tuple(x.shape), str(x.dtype))  # noqa: E731
    bad = [(jax.tree_util.keystr(k), shape(g), shape(w)) for (k, g), w in
           zip(jax.tree_util.tree_leaves_with_path(params),
               jax.tree_util.tree_leaves(want)) if shape(g) != shape(w)]
    if bad:
        raise ValueError(f"seeded leaves differ from the program's deployed "
                         f"layout: {bad[:4]}")
