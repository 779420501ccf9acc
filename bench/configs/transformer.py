"""The benchmark's side of a tapped transformer configuration.

Both configurations here (``coca-ast``, ``phi-3-vision-4.2b``) run the
program's ``repro.models.prefill``: a stack of pre-norm blocks over
[frontend patches, text tokens], a pooled tap after chosen blocks projected
to unit vectors, and a class head on the pooled final state.  This module
holds what the benchmark needs of such a model and owns itself:

* :func:`make_params` — random weights from a seed, made on the device in
  one jitted call, in the layout ``prefill`` reads and in the type they are
  served in (bfloat16 blocks and embeddings, float32 taps and head);
* :func:`forward` — the plain reference: float32 ``jax.numpy`` at
  ``precision`` (``highest`` for the reference), layer by layer over blocks
  of rows, importing nothing of the program;
* ``quant="fp8"`` — the control's weights, cast layer by layer: the
  bfloat16 leaves in float8 e4m3 with one scale per tensor, the float32
  leaves in bfloat16.

The reference follows the equations the program implements, which depart
from the published models in ways that do not change the shapes: causal
attention with interleaved-pair rotary embeddings for every configuration
(AST itself is bidirectional with learned positions; Phi-3 rotates the two
halves of a head), frontend patches as given embeddings, and GELU in its
tanh form.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BF16_GROUPS = ("decoder", "embed")


def tap_layers(m: dict) -> list[int]:
    k = m["tap_every"]
    return list(range(k - 1, m["num_layers"], k)) if k > 0 else []


def _norm_params(m, lead=()):
    p = {"scale": jnp.ones(lead + (m["d_model"],), jnp.float32)}
    if m.get("norm", "rmsnorm") == "layernorm":
        p["bias"] = jnp.zeros(lead + (m["d_model"],), jnp.float32)
    return p


def _tn(key, shape, scale):
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                               jnp.float32)


def make_params(key, cfg: dict):
    """Random weights in ``prefill``'s layout, made in one jitted call."""
    m = cfg["model"]
    d, h, hk = m["d_model"], m["num_heads"], m["kv_heads"]
    hd = m.get("head_dim") or d // h
    ff, V, G = m["d_ff"], m["vocab_size"], m["num_layers"]
    n_taps = len(tap_layers(m))
    gelu = m.get("act", "swiglu") == "gelu"

    def build(key):
        ks = iter(jax.random.split(key, 16))
        attn = {"wq": _tn(next(ks), (G, d, h, hd), d ** -0.5),
                "wk": _tn(next(ks), (G, d, hk, hd), d ** -0.5),
                "wv": _tn(next(ks), (G, d, hk, hd), d ** -0.5),
                "wo": _tn(next(ks), (G, h, hd, d), (h * hd) ** -0.5)}
        if gelu:
            mlp = {"wi": _tn(next(ks), (G, d, ff), d ** -0.5),
                   "wo": _tn(next(ks), (G, ff, d), ff ** -0.5)}
        else:
            mlp = {"wi_gate": _tn(next(ks), (G, d, ff), d ** -0.5),
                   "wi_up": _tn(next(ks), (G, d, ff), d ** -0.5),
                   "wo": _tn(next(ks), (G, ff, d), ff ** -0.5)}
        layer = {"norm1": _norm_params(m, (G,)), "attn": attn,
                 "norm2": _norm_params(m, (G,)), "mlp": mlp}
        p = {"embed": {"tok": _tn(next(ks), (V, d), 1.0),
                       "unembed": _tn(next(ks), (d, V), d ** -0.5)},
             "decoder": {"layers": [layer]},
             "final_norm": _norm_params(m),
             "taps": {"proj": _tn(next(ks), (n_taps, d, m["sem_dim"]),
                                  d ** -0.5)},
             "cls_head": _tn(next(ks), (d, m["num_classes"]), d ** -0.5)}
        for g in BF16_GROUPS:
            p[g] = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p[g])
        return p

    return jax.jit(build)(key)


def _fp8(a):
    """float8 e4m3 with one scale per tensor (max |w| maps to 448)."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _cast(tree, quant: str, group: str):
    """Float32 copies of a weight group; with ``quant="fp8"`` (the
    control) the bfloat16 groups go through float8 e4m3 and the float32
    ones through bfloat16."""
    if quant == "none":
        f = lambda a: a.astype(jnp.float32)              # noqa: E731
    elif group in BF16_GROUPS:
        f = _fp8
    else:
        f = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
    return jax.tree.map(f, tree)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _norm(p, x, kind):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]
    var = (x ** 2).mean(-1, keepdims=True)
    return x / jnp.sqrt(var + 1e-5) * p["scale"]


def _rope(x, pos, theta):
    """Rotate each interleaved pair (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None] * inv[None, :]                   # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (x + 0.044715 * x ** 3)))


def _layer(h, lp, m, prec, quant):
    f32 = _cast(lp, quant, "decoder")
    a, mlp = f32["attn"], f32["mlp"]
    norm = m.get("norm", "rmsnorm")
    S = h.shape[1]
    pos = jnp.arange(S, dtype=jnp.float32)
    x = _norm(f32["norm1"], h, norm)
    q = jnp.einsum("bsd,dhk->bshk", x, a["wq"], precision=prec)
    k = jnp.einsum("bsd,dhk->bshk", x, a["wk"], precision=prec)
    v = jnp.einsum("bsd,dhk->bshk", x, a["wv"], precision=prec)
    theta = m.get("rope_theta", 10_000.0)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bshk,bthk->bhst", q, k, precision=prec) / math.sqrt(
        q.shape[-1])
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    att = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", att, v, precision=prec)
    h = h + jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=prec)
    x = _norm(f32["norm2"], h, norm)
    if "wi" in mlp:
        y = _gelu_tanh(jnp.matmul(x, mlp["wi"], precision=prec))
    else:
        y = (jax.nn.silu(jnp.matmul(x, mlp["wi_gate"], precision=prec))
             * jnp.matmul(x, mlp["wi_up"], precision=prec))
    h = h + jnp.matmul(y, mlp["wo"], precision=prec)
    return h, h.mean(axis=1)


_LAYER = jax.jit(_layer, static_argnums=(2, 3, 4))


def _embed(tok, batch, quant):
    tok = jnp.take(_cast(tok, quant, "embed"), batch["tokens"], axis=0)
    return jnp.concatenate([batch["frontend"].astype(jnp.float32), tok], 1)


def _head(params, h, pooled, m, prec, quant):
    p = _cast(params, quant, "head")
    hf = _norm(p["final_norm"], h, m.get("norm", "rmsnorm"))
    cls = jnp.matmul(hf.mean(axis=1), p["cls_head"], precision=prec)
    sel = jnp.stack([pooled[j] for j in tap_layers(m)], axis=1)  # (B, T, d)
    z = jnp.einsum("btd,tds->bts", sel, p["taps"]["proj"], precision=prec)
    z = jax.nn.relu(z) + 1e-6
    return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-8), cls


_EMBED = jax.jit(_embed, static_argnums=(2,))
_HEAD = jax.jit(_head, static_argnums=(3, 4, 5))


def forward(params, batch_fn, rows: int, cfg: dict, *, precision="highest",
            quant: str = "none", block: int = 16):
    """Reference taps and class logits of ``rows`` frames.

    ``batch_fn(lo, hi)`` returns the inputs of rows ``lo:hi``
    (``{"tokens", "frontend"}``).  Rows go in blocks of ``block``, layer by
    layer; ``precision`` is the matmul precision (``"highest"`` for the
    reference).  Returns host arrays ``(taps (rows, T, sem), cls (rows,
    C))`` in float32."""
    import numpy as np
    m = cfg["model"]
    G = m["num_layers"]
    layers = params["decoder"]["layers"][0]
    head = {k: params[k] for k in ("final_norm", "cls_head", "taps")}
    sizes, hs = [], []
    for lo in range(0, rows, block):
        hi = min(lo + block, rows)
        batch = batch_fn(lo, hi)
        n = hi - lo
        if n < block:            # pad the last block to the compiled shape
            batch = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.repeat(a[-1:], block - n, axis=0)]), batch)
        sizes.append(n)
        hs.append(_EMBED(params["embed"]["tok"], batch, quant))
    pooled = [[] for _ in hs]
    for g in range(G):                       # layer by layer, all blocks
        lp = jax.tree.map(lambda a, g=g: a[g], layers)
        for i, h in enumerate(hs):
            hs[i], p = _LAYER(h, lp, _freeze(m), precision, quant)
            pooled[i].append(p)
        del lp
    taps_out, cls_out = [], []
    for h, p, n in zip(hs, pooled, sizes):
        taps, cls = jax.device_get(
            _HEAD(head, h, p, _freeze(m), precision, quant))
        taps_out.append(np.asarray(taps)[:n])
        cls_out.append(np.asarray(cls)[:n])
    return np.concatenate(taps_out), np.concatenate(cls_out)


class _freeze(dict):
    """A hashable dict, so a configuration can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
