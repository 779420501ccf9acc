"""The benchmark's side of the ``granite_h`` configuration
(``granite-4.0-h-small``, ``granitemoehybrid``).

The program runs it through ``repro.models.prefill`` like the dense
configurations: a stack of pre-norm blocks over [frontend positions, text
tokens], pooled taps after chosen blocks, a class head on the pooled final
state.  Each block is a mixer (Mamba-2, or GQA attention at index 5 of each
period of 10) and then a mixture of experts with a shared expert.  This
module holds what the benchmark needs of it and owns itself:

* :func:`make_params` — random weights from a seed, made on the device, in
  the layout ``prefill`` reads and in the type they are served in
  (bfloat16 blocks and embeddings, float32 taps and head);
* :func:`forward` — the plain reference: float32 ``jax.numpy`` at
  ``precision`` (``highest`` for the reference), layer by layer over
  blocks of rows, importing nothing of the program.  It follows the HF
  ``granitemoehybrid`` equations:

  - Mamba-2: in-projection to z, x, B, C and dt; causal depthwise conv of
    width 4 with bias, then SiLU; dt = softplus(dt + bias); A = −exp(A_log);
    the recurrence **position by position** (not chunked, so it checks the
    program's chunked scan): hₜ = exp(dtₜ·A)·hₜ₋₁ + dtₜ·Bₜxₜ,
    yₜ = Cₜhₜ + D·xₜ; then y·SiLU(z), RMS-normed over the inner width, and
    the out-projection;
  - causal GQA attention with no rotary (NoPE) and scores scaled by
    ``attention_multiplier`` (1/128);
  - h += 0.22·mixer(norm h), then h += 0.22·(experts + shared)(norm h),
    the router scoring all experts, each token's gates the softmax over
    its top-10 router logits;
  - input embeddings ×12;
* ``quant="fp8"`` — the control, one precision step below what the
  configuration states for what it states in bfloat16: the weights (cast
  layer by layer: the bfloat16 leaves in float8 e4m3 with one scale per
  tensor, the float32 leaves in bfloat16) and the activations the
  bfloat16 program carries between blocks (rounded to float8 e4m3, one
  scale per block of rows): the residual stream after each add, the
  normed input of each mixer and MoE (the projections' and router's
  operand), and the pooled vectors the taps and the class head read.
  The activations matter here as in no dense configuration: float8
  weights over float32 activations read closer to the reference than the
  bfloat16 program, and the pooled vectors carry most of the control's
  gap;
* :func:`work` — the operations and bytes of one backbone call and of its
  parts (the SSD scan, the held experts), from shapes, for the per-layer
  readers.

Departures from the published model, the same in program and reference:
the frontend stub's given embeddings stand in for the token embeddings of
the first ``frontend_len`` positions; a class head on the pooled final
state stands in for the tied LM head (so ``logits_scaling`` is unused);
one period of 10 layers is run (``num_hidden_layers``); each layer holds
18 of its 72 experts, indices 0-17, and computes only their part (the
other three quarters would lie on three more chips of the stated
deployment); the weights are random.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs.transformer import BF16_GROUPS, _freeze, _tn, tap_layers

CONFIG = Path(__file__).with_name("granite_4_0_h_small.json")
TRAFFIC = Path(__file__).parents[1] / "traffic" / (
    "cls100-zipf-closed-fixedmix.json")


def layer_kinds(m: dict) -> list[str]:
    return ["attn" if i % m["attn_every"] == m["attn_offset"] else "ssm"
            for i in range(m["num_layers"])]


def _dims(m: dict) -> dict:
    s, e = m["ssm"], m["moe"]
    d_in = s["expand"] * m["d_model"]
    return {"d": m["d_model"], "h": m["num_heads"], "hk": m["kv_heads"],
            "hd": m["head_dim"], "d_in": d_in, "H": d_in // s["head_dim"],
            "P": s["head_dim"], "N": s["d_state"], "K": s["d_conv"],
            "E": e["num_experts"], "topk": e["top_k"], "ff": e["d_expert"],
            "fs": e["d_shared"], "lo": e["held"][0], "n": e["held"][1],
            "chunk": s["chunk"]}


def _bf16(tree):
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)


def make_params(key, cfg: dict):
    """Random weights in ``prefill``'s layout: one jitted call per layer
    kind and one for the embedding and head, so that no call holds more
    than a layer in float32."""
    m = cfg["model"]
    D = _dims(m)
    d, d_in, N, H = D["d"], D["d_in"], D["N"], D["H"]

    def moe(ks):
        n, ff, fs = D["n"], D["ff"], D["fs"]
        return {"router": _tn(next(ks), (d, D["E"]), d ** -0.5),
                "wi_gate": _tn(next(ks), (n, d, ff), d ** -0.5),
                "wi_up": _tn(next(ks), (n, d, ff), d ** -0.5),
                "wo": _tn(next(ks), (n, ff, d), ff ** -0.5),
                "shared": {"wi_gate": _tn(next(ks), (d, fs), d ** -0.5),
                           "wi_up": _tn(next(ks), (d, fs), d ** -0.5),
                           "wo": _tn(next(ks), (fs, d), fs ** -0.5)}}

    def layer(key, kind):
        ks = iter(jax.random.split(key, 24))
        p = {"norm1": {"scale": jnp.ones((d,))},
             "norm2": {"scale": jnp.ones((d,))}, "moe": moe(ks)}
        if kind == "attn":
            h, hk, hd = D["h"], D["hk"], D["hd"]
            p["attn"] = {"wq": _tn(next(ks), (d, h, hd), d ** -0.5),
                         "wk": _tn(next(ks), (d, hk, hd), d ** -0.5),
                         "wv": _tn(next(ks), (d, hk, hd), d ** -0.5),
                         "wo": _tn(next(ks), (h, hd, d), (h * hd) ** -0.5)}
        else:
            K = D["K"]
            p["ssm"] = {
                "w_x": _tn(next(ks), (d, d_in), d ** -0.5),
                "w_z": _tn(next(ks), (d, d_in), d ** -0.5),
                "w_b": _tn(next(ks), (d, N), d ** -0.5),
                "w_c": _tn(next(ks), (d, N), d ** -0.5),
                "w_dt": _tn(next(ks), (d, H), d ** -0.5),
                "conv_x": _tn(next(ks), (K, d_in), 0.3),
                "conv_b": _tn(next(ks), (K, N), 0.3),
                "conv_c": _tn(next(ks), (K, N), 0.3),
                "conv_x_bias": _tn(next(ks), (d_in,), 0.1),
                "conv_b_bias": _tn(next(ks), (N,), 0.1),
                "conv_c_bias": _tn(next(ks), (N,), 0.1),
                "a_log": jnp.log(jnp.linspace(1.0, 16.0, H)),
                "dt_bias": jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, H))),
                "d_skip": jnp.ones((H,)),
                "norm_scale": jnp.ones((d_in,)),
                "w_out": _tn(next(ks), (d_in, d), d_in ** -0.5)}
        return jax.tree.map(lambda a: a[None].astype(jnp.bfloat16), p)

    def rest(key):
        ks = iter(jax.random.split(key, 4))
        return {"embed": _bf16({"tok": _tn(next(ks), (m["vocab_size"], d),
                                           1.0)}),
                "final_norm": {"scale": jnp.ones((d,))},
                "taps": {"proj": _tn(next(ks), (len(tap_layers(m)), d,
                                                m["sem_dim"]), d ** -0.5)},
                "cls_head": _tn(next(ks), (d, m["num_classes"]), d ** -0.5)}

    build = jax.jit(layer, static_argnums=(1,))
    keys = jax.random.split(key, m["num_layers"] + 1)
    p = jax.jit(rest)(keys[-1])
    p["decoder"] = {"layers": [build(keys[i], kind)
                               for i, kind in enumerate(layer_kinds(m))]}
    return p


def _fp8(a):
    """float8 e4m3 rounding (4 exponent and 3 mantissa bits) with one
    scale per tensor, as an explicit ``reduce_precision``: a compiler may
    drop a float32 -> float8 -> float32 convert pair as excess precision
    (on a v5e the activations' pairs were dropped), never this op."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 224.0
    return jax.lax.reduce_precision(a / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _cast(tree, quant: str, group: str):
    """Float32 copies of a weight group; with ``quant="fp8"`` (the
    control) the bfloat16 groups go through float8 e4m3 and the float32
    ones through bfloat16."""
    if quant == "none":
        f = lambda a: a.astype(jnp.float32)              # noqa: E731
    elif group in BF16_GROUPS:
        f = _fp8
    else:
        f = lambda a: jax.lax.reduce_precision(          # noqa: E731
            a.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)
    return jax.tree.map(f, tree)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _rms(x, scale, eps=1e-5):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, wg, wu, wo, prec):
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, wg, precision=prec))
                      * jnp.matmul(x, wu, precision=prec), wo,
                      precision=prec)


def _conv(x, w, b):
    """Causal depthwise conv (K taps) with bias, then SiLU."""
    K, S = w.shape[0], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(pad[:, i:i + S] * w[i] for i in range(K)) + b)


def ssd_sequential(x, dt, A, B, C, prec):
    """The Mamba-2 recurrence, one position at a time.  x (b, S, H, P),
    dt (b, S, H), A (H,), B/C (b, S, N) -> y (b, S, H, P), without D."""
    b, S, H, P = x.shape

    def step(h, inp):
        xt, dtt, Bt, Ct = inp
        h = (h * jnp.exp(dtt * A)[:, :, None, None]
             + (dtt[:, :, None, None] * Bt[:, None, :, None]
                * xt[:, :, None, :]))
        return h, jnp.einsum("bn,bhnp->bhp", Ct, h, precision=prec)

    h0 = jnp.zeros((b, H, B.shape[-1], P), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1)


def mamba_mixer(s, x, m, prec):
    """The Mamba-2 mixer of one layer on normed input x (b, S, d)."""
    D = _dims(m)
    b, S, _ = x.shape
    z = jnp.matmul(x, s["w_z"], precision=prec)
    xs = _conv(jnp.matmul(x, s["w_x"], precision=prec), s["conv_x"],
               s["conv_x_bias"])
    Bm = _conv(jnp.matmul(x, s["w_b"], precision=prec), s["conv_b"],
               s["conv_b_bias"])
    Cm = _conv(jnp.matmul(x, s["w_c"], precision=prec), s["conv_c"],
               s["conv_c_bias"])
    dt = jax.nn.softplus(jnp.matmul(x, s["w_dt"], precision=prec)
                         + s["dt_bias"])
    xh = xs.reshape(b, S, D["H"], D["P"])
    y = ssd_sequential(xh, dt, -jnp.exp(s["a_log"]), Bm, Cm, prec)
    y = (y + s["d_skip"][:, None] * xh).reshape(b, S, D["d_in"])
    y = _rms(y * jax.nn.silu(z), s["norm_scale"])
    return jnp.matmul(y, s["w_out"], precision=prec)


def attention(a, x, m, prec):
    """Causal GQA attention with no rotary, scores x ``attn_scale``."""
    S = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, a["wq"], precision=prec)
    k = jnp.einsum("bsd,dhk->bshk", x, a["wk"], precision=prec)
    v = jnp.einsum("bsd,dhk->bshk", x, a["wv"], precision=prec)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bshk,bthk->bhst", q, k,
                   precision=prec) * m["attn_scale"]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    att = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthk->bshk", att, v, precision=prec)
    return jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=prec)


def experts(e, x, m, prec):
    """The held experts' gated part and the shared expert, every held
    expert computed densely over all tokens and weighted by its gate (0
    where the token did not choose it)."""
    D = _dims(m)
    logits = jnp.matmul(x, e["router"], precision=prec)         # (b, S, E)
    top, idx = jax.lax.top_k(logits, D["topk"])
    gates = jax.nn.softmax(top, axis=-1)
    held = jnp.arange(D["lo"], D["lo"] + D["n"])
    g = jnp.einsum("bsk,bske->ebs", gates,
                   (idx[..., None] == held).astype(jnp.float32))
    hid = (jax.nn.silu(jnp.einsum("bsd,edf->ebsf", x, e["wi_gate"],
                                  precision=prec))
           * jnp.einsum("bsd,edf->ebsf", x, e["wi_up"], precision=prec))
    y = jnp.einsum("ebsf,efd,ebs->bsd", hid, e["wo"], g, precision=prec)
    sh = e["shared"]
    return y + _swiglu(x, sh["wi_gate"], sh["wi_up"], sh["wo"], prec)


def _layer(h, lp, m, kind, prec, quant):
    f32 = _cast(lp, quant, "decoder")
    r = m["residual_multiplier"]
    x = _act(_rms(h, f32["norm1"]["scale"]), quant)
    mix = (attention(f32["attn"], x, m, prec) if kind == "attn"
           else mamba_mixer(f32["ssm"], x, m, prec))
    h = _act(h + r * mix, quant)
    x = _act(_rms(h, f32["norm2"]["scale"]), quant)
    h = _act(h + r * experts(f32["moe"], x, m, prec), quant)
    return h, _act(h.mean(axis=1), quant)


def _act(x, quant):
    """An activation the program carries in bfloat16, as the control
    carries it (float8 e4m3)."""
    return _fp8(x) if quant == "fp8" else x


_LAYER = jax.jit(_layer, static_argnums=(2, 3, 4, 5))


def _embed(tok, batch, mult, quant):
    tok = jnp.take(_cast(tok, quant, "embed"), batch["tokens"], axis=0)
    return _act(mult * jnp.concatenate(
        [batch["frontend"].astype(jnp.float32), tok], 1), quant)


def _head(params, h, pooled, m, prec, quant):
    p = _cast(params, quant, "head")
    hf = _act(_rms(h, p["final_norm"]["scale"]), quant)
    cls = jnp.matmul(_act(hf.mean(axis=1), quant), p["cls_head"],
                     precision=prec)
    sel = jnp.stack([pooled[j] for j in tap_layers(m)], axis=1)  # (B, T, d)
    z = jnp.einsum("btd,tds->bts", sel, p["taps"]["proj"], precision=prec)
    z = jax.nn.relu(z) + 1e-6
    return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-8), cls


_EMBED = jax.jit(_embed, static_argnums=(2, 3))
_HEAD = jax.jit(_head, static_argnums=(3, 4, 5))


def forward(params, batch_fn, rows: int, cfg: dict, *, precision="highest",
            quant: str = "none", block: int = 8):
    """Reference taps and class logits of ``rows`` frames.

    ``batch_fn(lo, hi)`` returns the inputs of rows ``lo:hi``
    (``{"tokens", "frontend"}``).  Rows go in blocks of ``block``, layer by
    layer; ``precision`` is the matmul precision (``"highest"`` for the
    reference).  Returns host arrays ``(taps (rows, T, sem), cls (rows,
    C))`` in float32."""
    m = _freeze(cfg["model"])
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
        hs.append(_EMBED(params["embed"]["tok"], batch,
                         float(m["embedding_multiplier"]), quant))
    pooled = [[] for _ in hs]
    for lp, kind in zip(params["decoder"]["layers"], layer_kinds(m)):
        lp = jax.tree.map(lambda a: a[0], lp)
        for i, h in enumerate(hs):
            hs[i], p = _LAYER(h, lp, m, kind, precision, quant)
            pooled[i].append(p)
        del lp
    taps_out, cls_out = [], []
    for h, p, n in zip(hs, pooled, sizes):
        taps, cls = jax.device_get(_HEAD(head, h, p, m, precision, quant))
        taps_out.append(np.asarray(taps)[:n])
        cls_out.append(np.asarray(cls)[:n])
    return np.concatenate(taps_out), np.concatenate(cls_out)


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------


def work(m: dict, tokens: int, rows: int) -> dict:
    """Operations and bytes of one backbone call of ``rows`` frames.

    ``call_flops``: the whole call — 2 x matmul parameters x positions for
    the projections, the causal attention products (S(S+1)/2 pairs per
    head), the chunked SSD (causal pairs within a chunk), the router, the
    shared expert for every token and the held experts for the
    assignments that land on them (each token's top-k over a uniform
    router: ``top_k · held / num_experts`` per token; random routers are
    near uniform), the taps and the class head.  ``ssd``: (FLOPs, bytes)
    of one layer's scan: x·dt in and y out in bfloat16, the float32
    cumulative decay, B and C, each once.  ``experts``: (FLOPs, bytes) of
    one layer's held-expert matmuls: the held weights once, the routed
    rows in and out once, the hidden rows out and in once."""
    D = _dims(m)
    d, d_in, H, P, N, c = D["d"], D["d_in"], D["H"], D["P"], D["N"], \
        D["chunk"]
    S = m["frontend_len"] + tokens
    T = rows * S
    kinds = layer_kinds(m)
    n_ssm, n_attn = kinds.count("ssm"), kinds.count("attn")
    nc = -(-S // c)
    ssd_f = rows * nc * (N * c * (c + 1) + H * (P * c * (c + 1)
                                                + 4 * c * N * P))
    ssd_b = rows * nc * c * (2 * d_in * 2 + H * 4 + 2 * N * 2)
    mamba = (2 * T * d * (2 * d_in + 2 * N + H) + 2 * T * d_in * d
             + 2 * T * D["K"] * (d_in + 2 * N) + ssd_f)
    h, hk, hd = D["h"], D["hk"], D["hd"]
    attn = (2 * T * d * hd * (h + 2 * hk) + 2 * T * h * hd * d
            + 2 * 2 * rows * h * hd * S * (S + 1) / 2)
    routed = T * D["topk"] * D["n"] / D["E"]
    exp_f = 2 * routed * d * D["ff"] * 3
    exp_b = 2 * (D["n"] * d * D["ff"] * 3 + routed * (2 * d + 2 * D["ff"]))
    moe = 2 * T * d * D["E"] + exp_f + 2 * T * d * D["fs"] * 3
    heads = rows * (2 * len(tap_layers(m)) * d * m["sem_dim"]
                    + 2 * d * m["num_classes"])
    return {"call_flops": n_ssm * mamba + n_attn * attn
            + m["num_layers"] * moe + heads,
            "ssd": (ssd_f, ssd_b), "experts": (exp_f, exp_b),
            "ssm_layers": n_ssm, "moe_layers": m["num_layers"]}


def cell_work() -> dict:
    """:func:`work` of the ``granite-h-serve-fixedmix`` cell's call: this
    configuration at its traffic's ``max_slots`` rows."""
    cfg = json.loads(CONFIG.read_text())
    slots = json.loads(TRAFFIC.read_text())["max_slots"]
    return work(cfg["model"], cfg["tokens"], slots)


def roofline_share(ctx, part: str, match) -> float | None:
    """A part's share of its roofline in a traced run: the least time of
    its work in the window's backbone calls over the device time of the
    ops ``match`` accepts."""
    _, calls = ctx.lib.module_seconds(ctx.trace,
                                      lambda n: "bench_backbone" in n)
    s, n = ctx.lib.op_seconds(ctx.trace, match)
    if not calls or not n or s <= 0:
        return None
    w = cell_work()
    layers = w["ssm_layers"] if part == "ssd" else w["moe_layers"]
    least = ctx.counts.roofline_s(*w[part], ctx.peaks)
    return 100.0 * calls * layers * least / s

