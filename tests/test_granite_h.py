"""granite-4.0-h-small (``granitemoehybrid``) through the program's normal
path, against the plain reference ``bench/configs/granite_h.py`` (float32
``jax.numpy`` at ``highest``, importing nothing of the program), at a
small size on the CPU with seeded random weights.

The program runs here in float32 on the reference's weights (bfloat16
values), so every gap is float32 rounding in a different order of
operations: the chunked SSD against the position-by-position recurrence,
grouped matmuls against dense ones.  The tolerances below say so.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import granite_h
from repro.models import prefill
from repro.models.config import ModelConfig, MoEConfig, SSMConfig
from repro.models import attention as attn_mod
from repro.models import mamba2, moe
from repro.models.transformer import decode_step

ROOT = Path(__file__).resolve().parents[1]

# One whole period (9 Mamba-2 layers, attention at index 5) at small
# widths; 8 experts, top-3, two held; chunk 8 over 20 positions, so the
# scan crosses chunk boundaries and pads its tail.
TINY = {
    "name": "granite-tiny", "family": "hybrid", "num_layers": 10,
    "d_model": 64, "num_heads": 4, "kv_heads": 2, "head_dim": 16,
    "d_ff": 0, "vocab_size": 512, "attn_every": 10, "attn_offset": 5,
    "moe": {"num_experts": 8, "top_k": 3, "d_expert": 32, "d_shared": 48,
            "held": [0, 2]},
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
            "chunk": 8, "conv_bias": True},
    "position": "nope", "attn_scale": 0.0625, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "norm": "rmsnorm", "act": "swiglu",
    "tie_embeddings": True, "frontend": "audio", "frontend_len": 12,
    "tap_every": 2, "sem_dim": 32, "num_classes": 10, "dtype": "float32",
}
TOKENS = 8
ROWS = 3


def _cfg(**over):
    m = json.loads(json.dumps(TINY))
    for k, v in over.items():
        if isinstance(v, dict):
            m[k].update(v)
        else:
            m[k] = v
    return {"model": m, "tokens": TOKENS}


def _batch(m, rows=ROWS, seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"tokens": jax.random.randint(k1, (rows, TOKENS), 0,
                                         m["vocab_size"]),
            "frontend": jax.random.normal(k2, (rows, m["frontend_len"],
                                               m["d_model"]))}


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _reference(params, batch, cfg, block=ROWS):
    return granite_h.forward(
        params, lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], batch),
        batch["tokens"].shape[0], cfg, block=block)


def test_model_config_from_json_builds_nested_groups():
    m = json.loads((ROOT / "bench/configs/granite_4_0_h_small.json")
                   .read_text())["model"]
    cfg = ModelConfig(**m)
    assert isinstance(cfg.moe, MoEConfig) and isinstance(cfg.ssm, SSMConfig)
    assert cfg.moe.held == (0, 18) and cfg.moe.num_held == 18
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_shared) == (
        72, 10, 1536)
    assert (cfg.ssm.chunk, cfg.ssm.conv_bias) == (256, True)
    assert [cfg.layer_kind(i) for i in range(10)] == (
        ["ssm"] * 5 + ["attn"] + ["ssm"] * 4)
    assert cfg.tap_layers() == (1, 3, 5, 7, 9)
    hash(cfg)                     # a static argument of jit
    with pytest.raises(ValueError):
        MoEConfig(num_experts=8, top_k=2, d_expert=4, held=(6, 4))


@pytest.mark.parametrize("seed", [0, 7])
def test_prefill_matches_reference(seed):
    """Taps and class logits of the whole period through ``prefill``.
    Tolerance: float32 rounding through 10 layers in a different order
    (chunked scan, grouped matmuls); a wrong equation moves them by
    orders of magnitude more (see the multiplier test)."""
    cfg = _cfg()
    m = cfg["model"]
    params = granite_h.make_params(jax.random.PRNGKey(seed), cfg)
    batch = _batch(m, seed=seed + 1)
    _, _, taps, cls = prefill(_f32(params), batch, ModelConfig(**m))
    r_taps, r_cls = _reference(params, batch, cfg)
    np.testing.assert_allclose(np.asarray(taps), r_taps, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cls), r_cls, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("S", [16, 20])
def test_chunked_ssd_matches_sequential(use_kernel, S):
    """The program's Mamba-2 mixer (chunked scan: the jnp oracle, or the
    Pallas kernel in interpret mode) against the reference's recurrence
    position by position, across chunk boundaries (chunk 8) and, at 20
    positions, with a padded tail.  Tolerance: float32 rounding of the
    chunked form's exp(la_i − la_j) against step-by-step products."""
    cfg = _cfg()
    m = cfg["model"]
    mc = ModelConfig(**m)
    p = _f32(jax.tree.map(lambda a: a[0], granite_h.make_params(
        jax.random.PRNGKey(3), cfg)["decoder"]["layers"][0]["ssm"]))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, m["d_model"]))
    got = mamba2.mamba_fwd(p, x, mc, use_kernel=use_kernel)
    want = granite_h.mamba_mixer(p, x, m, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _full_moe_params(key, m):
    """One layer's MoE with every expert's weights (the uncut layer)."""
    d, e = m["d_model"], m["moe"]
    ks = jax.random.split(key, 7)
    n, ff, fs = e["num_experts"], e["d_expert"], e["d_shared"]
    tn = lambda k, s, sc: sc * jax.random.normal(k, s)      # noqa: E731
    return {"router": tn(ks[0], (d, n), d ** -0.5),
            "wi_gate": tn(ks[1], (n, d, ff), d ** -0.5),
            "wi_up": tn(ks[2], (n, d, ff), d ** -0.5),
            "wo": tn(ks[3], (n, ff, d), ff ** -0.5),
            "shared": {"wi_gate": tn(ks[4], (d, fs), d ** -0.5),
                       "wi_up": tn(ks[5], (d, fs), d ** -0.5),
                       "wo": tn(ks[6], (fs, d), fs ** -0.5)}}


def _held(p, lo, n):
    return dict(p, **{k: p[k][lo:lo + n] for k in ("wi_gate", "wi_up",
                                                   "wo")})


@pytest.mark.parametrize("use_kernel", [False, True])
def test_quarters_add_up_to_the_uncut_layer(use_kernel):
    """The share test: four chips' held-expert parts, with the shared
    expert (which every chip computes alike) counted once, add up to the
    uncut reference layer, with either combine.  Tolerance: float32 sums in
    another order."""
    m = _cfg()["model"]
    p = _full_moe_params(jax.random.PRNGKey(5), m)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 20, m["d_model"]))
    shared = granite_h._swiglu(x, p["shared"]["wi_gate"],
                               p["shared"]["wi_up"], p["shared"]["wo"],
                               "highest")
    total = -3 * shared
    for q in range(4):
        mc = ModelConfig(**_cfg(moe={"held": [2 * q, 2]})["model"])
        total = total + moe.moe_fwd(_held(p, 2 * q, 2), x, mc,
                                    use_kernel=use_kernel).y
    uncut = granite_h.experts(p, x, _cfg(moe={"held": [0, 8]})["model"],
                              "highest")
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dropless_when_one_expert_takes_every_token(use_kernel):
    """Every assignment to a held expert is computed and combined, even
    when one held expert is chosen by every token (a capacity of 1.25 x the
    mean would drop most of them)."""
    m = _cfg()["model"]
    p = _full_moe_params(jax.random.PRNGKey(8), m)
    p["router"] = p["router"].at[:, 0].set(10.0)     # expert 0 always wins
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9),
                                  (2, 20, m["d_model"]))) + 0.5
    idx = jax.lax.top_k(x @ p["router"], 3)[1]
    assert bool((idx == 0).any(-1).all())
    held = _cfg(moe={"held": [0, 2]})["model"]
    got = moe.moe_fwd(_held(p, 0, 2), x, ModelConfig(**held),
                      use_kernel=use_kernel).y
    want = granite_h.experts(_held(p, 0, 2), x, held, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_nope_attention_and_multipliers():
    """NoPE attention with an explicit scale matches the reference and
    differs from the rotary form; the residual and embedding multipliers
    are applied: with the residual multiplier 0 every layer adds nothing
    and the class logits are those of the scaled embeddings alone."""
    cfg = _cfg()
    m = cfg["model"]
    mc = ModelConfig(**m)
    p = _f32(jax.tree.map(lambda a: a[0], granite_h.make_params(
        jax.random.PRNGKey(10), cfg)["decoder"]["layers"][5]["attn"]))
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 20, m["d_model"]))
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    got, _ = attn_mod.full_attention(p, x, mc, pos, causal=True)
    want = granite_h.attention(p, x, m, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    rope, _ = attn_mod.full_attention(
        p, x, dataclasses.replace(mc, position="rope"), pos, causal=True)
    assert float(jnp.abs(rope - got).max()) > 1e-2

    params = _f32(granite_h.make_params(jax.random.PRNGKey(12), cfg))
    batch = _batch(m)
    still = dataclasses.replace(mc, residual_multiplier=0.0)
    _, _, _, cls = prefill(params, batch, still)
    emb = 12.0 * jnp.concatenate(
        [batch["frontend"], params["embed"]["tok"][batch["tokens"]]], 1)
    hf = emb / jnp.sqrt((emb ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(cls),
                               np.asarray(hf.mean(1) @ params["cls_head"]),
                               rtol=1e-4, atol=1e-5)
    _, _, _, moved = prefill(params, batch, mc)
    assert float(jnp.abs(moved - cls).max()) > 1e-3


def test_prefill_then_decode_matches_longer_prefill():
    """Decoding one token through the caches (the SSM state with its conv
    tail, the NoPE KV cache) gives the logits a prefill over the longer
    sequence gives.  Tolerance: float32 rounding of the chunked scan
    against the recurrent step."""
    cfg = _cfg(frontend="none", frontend_len=0)
    m = cfg["model"]
    mc = ModelConfig(**m)
    params = _f32(granite_h.make_params(jax.random.PRNGKey(13),
                                        {"model": dict(m, frontend_len=0),
                                         "tokens": TOKENS}))
    toks = jax.random.randint(jax.random.PRNGKey(14), (2, 12), 0,
                              m["vocab_size"])
    full, _, _, _ = prefill(params, {"tokens": toks}, mc)
    _, caches, _, _ = prefill(params, {"tokens": toks[:, :11]}, mc,
                              max_len=12)
    step, _, _, _ = decode_step(params, toks[:, 11:], caches, mc)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, 0]), rtol=1e-4, atol=1e-4)
