"""int8 cache entries (bf16 per-class scales): quantization properties and
lookup parity.

The contract (docs/architecture.md, "Quantized entry layout"):

* **Round-trip bound** — ``|dequantize(quantize(x)) - x| <= scale/2``
  elementwise, where ``scale`` is the *stored* bf16 scale.  The bound is
  exact because rounding happens against the stored scale (rounding against
  the pre-cast f32 scale would add a ``127·|Δscale|`` slack term).
* **Kernel parity** — the quantized fused kernels (single-pass and
  class-tiled) dequantize in-register with the same elementwise op the
  reference materialises, so their scores are *bitwise* equal to
  ``lookup_all_layers_ref`` on the quantized table.
* **Drift vs. fp32** — quantization moves each cosine score by at most
  ``sqrt(d) * max_scale / 2`` (Cauchy–Schwarz on the per-element error
  against a unit-norm tap); the Eq.-2 combined score by at most twice that.
* **Agreement** — on separated tables (taps drawn near their class
  centroid — the deployment regime) hit/pred agree with fp32 on >= 99% of
  frames.  Random gaussian tables are the adversarial near-tie case and sit
  below that; the guarantee is drift-bounded scores, not identical argmaxes.
* **Budget model** — the int8 slab is ~4x smaller, so
  ``pick_class_block(int8) >= pick_class_block(float32)``.

Runs under real hypothesis when installed, else the deterministic fallback
engine (strategies stay inside integers / sampled_from / composite).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                       allocate_subtable, allocate_subtables,
                                       dequantize_entries,
                                       dequantize_table, l2_normalize,
                                       lookup_all_layers,
                                       lookup_all_layers_ref,
                                       quantize_entries, quantize_table)

KEY = jax.random.PRNGKey(5)


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------


@st.composite
def entry_shapes(draw):
    L = draw(st.integers(min_value=1, max_value=5))
    I = draw(st.sampled_from([1, 7, 33, 100]))
    d = draw(st.sampled_from([8, 16, 32]))
    seed = draw(st.integers(min_value=0, max_value=2**16))
    amp = draw(st.sampled_from([1, 10, 1000]))
    return L, I, d, seed, amp


# ---------------------------------------------------------------------------
# round-trip bound
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(entry_shapes())
def test_quant_round_trip_within_half_scale(case):
    L, I, d, seed, amp = case
    x = amp * jax.random.normal(jax.random.PRNGKey(seed), (L, I, d))
    q, scale = quantize_entries(x)
    assert q.dtype == jnp.int8
    assert scale.dtype == jnp.bfloat16
    assert scale.shape == (L, I)
    deq = dequantize_entries(q, scale)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.asarray(scale.astype(jnp.float32))[..., None] / 2
    assert (err <= bound * (1 + 1e-6)).all(), \
        f"max excess {np.max(err - bound):.3e}"


def test_quant_zero_rows_round_trip_exactly():
    x = jnp.zeros((2, 5, 8))
    q, scale = quantize_entries(x)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(dequantize_entries(q, scale)), 0)


def test_quantize_table_round_trips_and_is_idempotent():
    entries = l2_normalize(jax.random.normal(KEY, (3, 20, 16)))
    table = CacheTable(entries, jnp.ones(20, bool), jnp.ones(3, bool))
    qt = quantize_table(table)
    assert qt.quantized and not table.quantized
    assert quantize_table(qt) is qt                   # no-op when quantized
    back = dequantize_table(qt)
    assert back.entry_scale is None
    assert dequantize_table(table) is table           # no-op when fp32
    err = np.abs(np.asarray(back.entries) - np.asarray(entries))
    bound = np.asarray(qt.entry_scale.astype(jnp.float32))[..., None] / 2
    assert (err <= bound * (1 + 1e-6)).all()


# ---------------------------------------------------------------------------
# kernel parity on quantized tables (bitwise vs. the dequantizing reference)
# ---------------------------------------------------------------------------


def _quant_world(B, I, L, d, seed, theta=0.05):
    key = jax.random.PRNGKey(seed)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    cmask = np.asarray(
        jax.random.bernoulli(jax.random.fold_in(key, 1), 0.8, (I,)),
        bool).copy()
    cmask[0] = True
    table = quantize_table(
        CacheTable(entries, jnp.asarray(cmask), jnp.ones(L, bool)))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=theta)
    return table, sems, cfg


@pytest.mark.parametrize("impl", ["fused_single", "fused_tiled"])
@pytest.mark.parametrize("B,I,L,d", [(16, 20, 4, 16), (37, 300, 3, 32)])
def test_quantized_kernel_parity_bitwise(impl, B, I, L, d):
    table, sems, cfg = _quant_world(B, I, L, d, seed=B + I)
    ref = lookup_all_layers_ref(table, sems, cfg)
    out = lookup_all_layers(table, sems, cfg, impl=impl)
    np.testing.assert_array_equal(np.asarray(out.hit), np.asarray(ref.hit))
    np.testing.assert_array_equal(np.asarray(out.pred), np.asarray(ref.pred))
    np.testing.assert_array_equal(np.asarray(out.exit_layer),
                                  np.asarray(ref.exit_layer))
    np.testing.assert_allclose(np.asarray(out.scores),
                               np.asarray(ref.scores), rtol=1e-5, atol=1e-6)
    assert np.asarray(ref.hit).any()


# ---------------------------------------------------------------------------
# drift vs. fp32 under the stated bound
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2**16))
def test_quantized_score_drift_bounded(seed):
    B, I, L, d = 24, 30, 3, 16
    key = jax.random.PRNGKey(seed)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    fp32 = CacheTable(entries, jnp.ones(I, bool), jnp.ones(L, bool))
    quant = quantize_table(fp32)
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.05)
    s_fp = np.asarray(lookup_all_layers_ref(fp32, sems, cfg).scores)
    s_q = np.asarray(lookup_all_layers_ref(quant, sems, cfg).scores)
    # per-element cosine drift <= sqrt(d)*max_scale/2 (unit-norm taps); the
    # Eq.-2 score is alpha*a1 + (1-alpha)*(a1-a2) so at most doubles it.
    max_scale = float(np.max(np.asarray(quant.entry_scale.astype(jnp.float32))))
    bound = 2 * np.sqrt(d) * max_scale / 2
    assert np.max(np.abs(s_q - s_fp)) <= bound + 1e-6


def test_quantized_agreement_on_separated_tables():
    """Deployment regime: taps drawn near their class centroid.  hit and
    pred must agree with fp32 on >= 99% of frames (random gaussian tables
    are the near-tie adversarial case and are NOT covered by this bound)."""
    B, I, L, d = 500, 20, 4, 32
    key = jax.random.PRNGKey(17)
    entries = l2_normalize(jax.random.normal(key, (L, I, d)))
    fp32 = CacheTable(entries, jnp.ones(I, bool), jnp.ones(L, bool))
    quant = quantize_table(fp32)
    lab = jax.random.randint(jax.random.fold_in(key, 1), (B,), 0, I)
    sems = (entries[:, lab, :].transpose(1, 0, 2)
            + 0.05 * jax.random.normal(jax.random.fold_in(key, 2), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.05)
    out_fp = lookup_all_layers_ref(fp32, sems, cfg)
    out_q = lookup_all_layers_ref(quant, sems, cfg)
    hit_agree = np.mean(np.asarray(out_fp.hit) == np.asarray(out_q.hit))
    pred_agree = np.mean(np.asarray(out_fp.pred) == np.asarray(out_q.pred))
    assert hit_agree >= 0.99, hit_agree
    assert pred_agree >= 0.99, pred_agree
    assert np.asarray(out_fp.hit).mean() > 0.5   # the case must exercise hits


# ---------------------------------------------------------------------------
# budget model + allocation plumbing
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=12),
       st.sampled_from([8, 16, 64, 256, 1024]))
def test_quantized_class_block_never_smaller(L, d):
    from repro.kernels.common import pick_class_block
    assert (pick_class_block(L, d, entry_dtype="int8")
            >= pick_class_block(L, d, entry_dtype="float32"))


def test_entry_row_bytes_model():
    from repro.kernels.common import entry_row_bytes
    assert entry_row_bytes(64, "float32") == 256
    assert entry_row_bytes(64, "int8") == 64 + 2      # payload + bf16 scale
    with pytest.raises(ValueError, match="unknown entry dtype"):
        entry_row_bytes(64, "int4")


def test_allocate_subtable_entry_dtype():
    entries = l2_normalize(jax.random.normal(KEY, (3, 16, 8)))
    x = jnp.zeros((3, 16), bool).at[:2, :5].set(True)   # (L, I) ACA indicator
    fp = allocate_subtable(entries, x)
    qt = allocate_subtable(entries, x, entry_dtype="int8")
    assert fp.entry_scale is None and qt.quantized
    np.testing.assert_array_equal(np.asarray(fp.class_mask),
                                  np.asarray(qt.class_mask))
    # masked-in rows round-trip within the bound; dtype carried end to end
    assert qt.entries.dtype == jnp.int8
    with pytest.raises(ValueError, match="unknown entry dtype"):
        allocate_subtable(entries, x, entry_dtype="fp8")


def test_stack_tables_rejects_mixed_dtypes():
    from repro.core.engine import _stack_tables
    entries = l2_normalize(jax.random.normal(KEY, (2, 8, 8)))
    fp = CacheTable(entries, jnp.ones(8, bool), jnp.ones(2, bool))
    qt = quantize_table(fp)
    stacked = _stack_tables([qt, qt])
    assert stacked.quantized and stacked.entries.shape[0] == 2
    with pytest.raises(ValueError, match="mixed"):
        _stack_tables([fp, qt])


def _assert_tables_equal(a, b):
    assert (a.entry_scale is None) == (b.entry_scale is None)
    for x, y in zip(a, b):
        if x is not None:
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("num_clients", [1, 5])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_batched_cut_matches_separate_cuts(dtype, num_clients):
    """One vmapped cut of K allocations is, bit for bit, the K cuts that
    allocate_subtable makes alone, whether stacked or listed."""
    entries = l2_normalize(jax.random.normal(KEY, (3, 16, 8)))
    xs = np.random.default_rng(num_clients).random((num_clients, 3, 16)) < .4
    xs[0, 1] = False                       # a layer left out of one cut
    stacked = allocate_subtables(entries, jnp.asarray(xs), entry_dtype=dtype)
    listed = allocate_subtables(entries, jnp.asarray(xs), entry_dtype=dtype,
                                stacked=False)
    assert stacked.entries.shape == (num_clients, 3, 16, 8)
    assert len(listed) == num_clients
    for k in range(num_clients):
        alone = allocate_subtable(entries, jnp.asarray(xs[k]),
                                  entry_dtype=dtype)
        _assert_tables_equal(alone, listed[k])
        _assert_tables_equal(
            alone, jax.tree_util.tree_map(lambda a, k=k: a[k], stacked))


def test_cluster_runs_quantized_end_to_end():
    """entry_dtype='int8' threads through allocation -> lookup -> merge for
    a full cluster round; hit ratio stays in the same ballpark as fp32."""
    from repro import api
    from repro.core import calibrate

    I, L, D, F, K, R = 10, 4, 16, 24, 3, 2
    cm = calibrate(np.linspace(2.0, 1.0, L + 1), np.full(L, D),
                   head_cost=0.5)
    key = jax.random.PRNGKey(0)
    centroids = jax.random.normal(key, (L, I, D))

    def taps_for(labels, seed):
        k = jax.random.PRNGKey(seed)
        lab = jnp.asarray(labels)
        sems = centroids[:, lab, :].transpose(1, 0, 2) + \
            0.3 * jax.random.normal(k, (len(labels), L, D))
        logits = (jax.nn.one_hot(lab, I) * 4.0
                  + jax.random.normal(jax.random.fold_in(k, 1),
                                      (len(labels), I)))
        return sems, logits

    rng = np.random.default_rng(3)
    labels = rng.integers(0, I, size=(R, K, F))
    shared = np.tile(np.arange(I), 8)

    hit_ratio = {}
    for dtype in ("float32", "int8"):
        cache = api.CacheConfig(num_classes=I, num_layers=L, sem_dim=D,
                                theta=0.05, entry_dtype=dtype)
        sim = api.SimulationConfig(cache=cache, round_frames=F,
                                   mem_budget=8_000.0)
        cluster = api.CocaCluster(sim, cm)
        cluster.bootstrap(jax.random.PRNGKey(0),
                          lambda lab: taps_for(lab, 999), shared)
        for r in range(R):
            cluster.step([api.FrameBatch(*taps_for(labels[r, k_],
                                                   7 + 13 * r + 131 * k_),
                                         labels=labels[r, k_])
                          for k_ in range(K)])
        hit_ratio[dtype] = cluster.result().hit_ratio
    assert hit_ratio["float32"] > 0
    assert abs(hit_ratio["int8"] - hit_ratio["float32"]) <= 0.05
