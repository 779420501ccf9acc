"""Pallas kernels vs. pure-jnp oracles: shape × dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def k(i):
    return jax.random.fold_in(KEY, i)


# ---------------------------------------------------------------------------
# cache_lookup
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,I,d", [(8, 20, 32), (37, 100, 64),
                                   (130, 257, 256), (1, 5, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cache_lookup_sweep(B, I, d, dtype):
    sem = jnp.abs(jax.random.normal(k(1), (B, d))).astype(dtype)
    entries = jnp.abs(jax.random.normal(k(2), (I, d)))
    entries = (entries / jnp.linalg.norm(entries, axis=1, keepdims=True))
    mask = jax.random.bernoulli(k(3), 0.8, (I,))
    mask = mask.at[0].set(True).at[min(1, I - 1)].set(True)
    a_prev = jnp.where(mask, jax.random.uniform(k(4), (B, I)), -1e9)
    a1, d1, p1 = ops.cache_lookup_layer(sem.astype(jnp.float32), entries,
                                        mask, a_prev)
    a2, d2, p2 = ref.cache_lookup_layer_ref(sem.astype(jnp.float32), entries,
                                            mask, a_prev)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(a1)[:, m], np.asarray(a2)[:, m],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


# ---------------------------------------------------------------------------
# cache_lookup_all_layers (fused full-pipeline kernel vs. the jnp oracle)
# ---------------------------------------------------------------------------

def _all_layer_case(B, I, L, d, theta, seed, *, class_keep=1.0, layer_keep=1.0,
                    n_active_classes=None):
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers,
                                           lookup_all_layers_ref)
    key = jax.random.PRNGKey(seed)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    if n_active_classes is not None:
        cmask = np.zeros(I, bool)
        cmask[:n_active_classes] = True
    else:
        cmask = np.asarray(
            jax.random.bernoulli(jax.random.fold_in(key, 1), class_keep, (I,)),
            bool).copy()
        cmask[0] = True
    lmask = np.asarray(
        jax.random.bernoulli(jax.random.fold_in(key, 2), layer_keep, (L,)),
        bool).copy()
    lmask[0] = True
    table = CacheTable(entries, jnp.asarray(cmask), jnp.asarray(lmask))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=theta)
    ref_out = lookup_all_layers_ref(table, sems, cfg)
    fused = lookup_all_layers(table, sems, cfg, impl="fused")
    np.testing.assert_array_equal(np.asarray(fused.hit), np.asarray(ref_out.hit))
    np.testing.assert_array_equal(np.asarray(fused.exit_layer),
                                  np.asarray(ref_out.exit_layer))
    np.testing.assert_array_equal(np.asarray(fused.pred),
                                  np.asarray(ref_out.pred))
    np.testing.assert_allclose(np.asarray(fused.scores),
                               np.asarray(ref_out.scores),
                               rtol=1e-4, atol=1e-5)
    assert fused.acc is None            # the fused path never materialises acc
    return ref_out


@pytest.mark.parametrize("B,I,L,d", [(8, 12, 5, 16),     # tiny, unaligned
                                     (37, 100, 6, 32),   # unaligned B and I
                                     (130, 257, 4, 64),  # >1 tile in B and I
                                     (1, 5, 3, 16)])     # single frame
def test_all_layer_lookup_parity_shapes(B, I, L, d):
    _all_layer_case(B, I, L, d, theta=0.03, seed=B + I)


def test_all_layer_lookup_parity_masked_classes():
    _all_layer_case(40, 64, 5, 32, theta=0.02, seed=7, class_keep=0.5)


def test_all_layer_lookup_parity_inactive_layers():
    out = _all_layer_case(40, 32, 8, 32, theta=0.02, seed=11, layer_keep=0.5)
    assert np.asarray(out.hit).any()    # case must actually exercise hits


def test_all_layer_lookup_parity_few_active_classes():
    # <2 active classes: a_b stays at NEG and the a_b <= NEG/2 guard fires.
    _all_layer_case(16, 12, 4, 16, theta=0.05, seed=13, n_active_classes=1)
    _all_layer_case(16, 12, 4, 16, theta=0.05, seed=17, n_active_classes=2)


def test_all_layer_lookup_parity_per_layer_theta():
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers,
                                           lookup_all_layers_ref)
    B, I, L, d = 24, 20, 4, 16
    key = jax.random.PRNGKey(23)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    table = CacheTable(entries, jnp.ones(I, bool), jnp.ones(L, bool))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d,
                      theta=(0.2, 0.1, 0.05, 0.02))
    ref_out = lookup_all_layers_ref(table, sems, cfg)
    fused = lookup_all_layers(table, sems, cfg, impl="fused")
    np.testing.assert_array_equal(np.asarray(fused.exit_layer),
                                  np.asarray(ref_out.exit_layer))
    np.testing.assert_array_equal(np.asarray(fused.pred),
                                  np.asarray(ref_out.pred))


# ---------------------------------------------------------------------------
# cache_lookup_all_layers_tiled (class-tile grid for huge-I tables)
# ---------------------------------------------------------------------------

def _tiled_case(B, I, L, d, theta, seed, *, i_block, class_keep=0.7,
                layer_keep=0.7):
    """Parity of the class-tiled kernel vs. the jnp oracle, with explicit
    control of the block size so grid revisits are actually exercised."""
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers_ref)
    from repro.kernels.cache_lookup import cache_lookup_all_layers_tiled
    key = jax.random.PRNGKey(seed)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    cmask = np.asarray(
        jax.random.bernoulli(jax.random.fold_in(key, 1), class_keep, (I,)),
        bool).copy()
    cmask[0] = True
    lmask = np.asarray(
        jax.random.bernoulli(jax.random.fold_in(key, 2), layer_keep, (L,)),
        bool).copy()
    lmask[0] = True
    table = CacheTable(entries, jnp.asarray(cmask), jnp.asarray(lmask))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=theta)
    ref_out = lookup_all_layers_ref(table, sems, cfg)
    scores, preds, exit_layer = cache_lookup_all_layers_tiled(
        sems, table.entries, table.class_mask, table.layer_mask,
        cfg.theta_vec(), alpha=cfg.alpha, i_block=i_block)
    np.testing.assert_array_equal(np.asarray(exit_layer),
                                  np.asarray(ref_out.exit_layer))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_out.scores),
                               rtol=1e-4, atol=1e-5)
    pred = np.take_along_axis(
        np.asarray(preds),
        np.minimum(np.asarray(exit_layer), L - 1)[:, None], axis=1)[:, 0]
    np.testing.assert_array_equal(pred, np.asarray(ref_out.pred))
    return ref_out


@pytest.mark.parametrize("I", [1024, 4096, 16384])
def test_tiled_lookup_parity_large_I(I):
    # I = 4096/16384 with L=12, d=64 are past the single-pass VMEM ceiling
    # at the real 16 MB budget when scaled to paper L·d; here we force small
    # blocks so every case streams multiple entry slabs through "VMEM".
    out = _tiled_case(37, I, 4, 32, theta=0.02, seed=I, i_block=512)
    assert np.asarray(out.hit).any()


@pytest.mark.parametrize("I", [300, 1000, 4097])
def test_tiled_lookup_parity_unaligned_I(I):
    # I neither a multiple of the block nor of I_TILE: padded classes must
    # never win the top-2 or shift the argmax class ids.
    _tiled_case(18, I, 5, 16, theta=0.02, seed=I, i_block=256)


def test_tiled_lookup_accumulator_carry_across_revisits():
    """Multiple batch tiles x multiple class blocks: the (B_TILE, L) top-2
    scratch must reset at block 0 of every batch-tile revisit and carry
    across the class blocks within one."""
    out = _tiled_case(260, 1500, 5, 32, theta=0.02, seed=3, i_block=256,
                      class_keep=0.6, layer_keep=0.8)
    assert np.asarray(out.hit).any()


@pytest.mark.parametrize("n_active", [1, 2])
def test_tiled_lookup_few_active_classes_across_blocks(n_active):
    """<2 active classes globally: m2 must stay at NEG through every block
    merge so the Eq.-2 guard yields d=0 (no hit), even when the active
    classes sit in different class blocks."""
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers_ref)
    from repro.kernels.cache_lookup import cache_lookup_all_layers_tiled
    B, I, L, d = 16, 700, 4, 16
    key = jax.random.PRNGKey(31 + n_active)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    cmask = np.zeros(I, bool)
    cmask[0] = True                      # block 0
    if n_active == 2:
        cmask[600] = True                # a later block (i_block=256)
    table = CacheTable(entries, jnp.asarray(cmask), jnp.ones(L, bool))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.05)
    ref_out = lookup_all_layers_ref(table, sems, cfg)
    scores, preds, exit_layer = cache_lookup_all_layers_tiled(
        sems, table.entries, table.class_mask, table.layer_mask,
        cfg.theta_vec(), alpha=cfg.alpha, i_block=256)
    np.testing.assert_array_equal(np.asarray(exit_layer),
                                  np.asarray(ref_out.exit_layer))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_out.scores),
                               rtol=1e-4, atol=1e-5)
    if n_active == 1:
        assert not np.asarray(ref_out.hit).any()   # guard must fire: no hits


def test_tiled_lookup_single_block_degenerates_to_single_pass():
    # i_block >= I: one class block — must equal the single-pass kernel.
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers)
    B, I, L, d = 24, 200, 4, 16
    key = jax.random.PRNGKey(29)
    entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
    table = CacheTable(entries, jnp.ones(I, bool), jnp.ones(L, bool))
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.03)
    single = lookup_all_layers(table, sems, cfg, impl="fused_single")
    tiled = lookup_all_layers(table, sems, cfg, impl="fused_tiled")
    np.testing.assert_array_equal(np.asarray(tiled.exit_layer),
                                  np.asarray(single.exit_layer))
    np.testing.assert_array_equal(np.asarray(tiled.pred),
                                  np.asarray(single.pred))
    np.testing.assert_allclose(np.asarray(tiled.scores),
                               np.asarray(single.scores), rtol=1e-5,
                               atol=1e-6)


def test_lookup_dispatch_picks_tiled_past_vmem_ceiling():
    from repro.kernels.common import pick_class_block, single_pass_fits
    # Paper scale fits the single-pass kernel; the north-star huge-I regime
    # must not.
    assert single_pass_fits(24, 1024, 64)
    assert not single_pass_fits(12, 8192, 64)
    assert not single_pass_fits(24, 16384, 64)
    # The chosen block is lane-aligned and its working set fits the budget.
    from repro.kernels.common import (I_TILE, lookup_tiled_vmem_bytes,
                                      vmem_budget_bytes)
    for L, d in [(12, 64), (24, 64), (24, 128), (6, 32)]:
        blk = pick_class_block(L, d)
        assert blk % I_TILE == 0
        assert lookup_tiled_vmem_bytes(L, blk, d) <= vmem_budget_bytes()


# ---------------------------------------------------------------------------
# double-buffered DMA pipeline (manual async copies, two-slot scratch)
# ---------------------------------------------------------------------------

def test_tiled_lookup_odd_block_counts():
    # 3 and 5 class blocks: the ping-pong slot sequence ends on either
    # parity, and the final block's prefetch guard (t+1 == n) must not fire.
    _tiled_case(24, 3 * 256, 4, 16, theta=0.02, seed=21, i_block=256)
    _tiled_case(24, 5 * 128 - 40, 4, 16, theta=0.02, seed=22, i_block=128)


def test_tiled_lookup_max_block_count_ping_pong():
    # i_block == I_TILE gives the maximal block count: every step computes
    # slot t%2 while the prefetch for t+1 lands in the opposite slot, so a
    # slot-reuse bug (overwriting the block still being consumed) shows up
    # as a parity break here.
    _tiled_case(16, 9 * 128, 3, 16, theta=0.02, seed=23, i_block=128)


def test_tiled_lookup_traces_once_across_rounds():
    """The pipelined kernel is one jit trace per (table, batch) shape — a
    round loop re-invoking it must NOT rebuild the DMA pipeline."""
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize)
    from repro.kernels import cache_lookup as kmod
    from tools.cocalint.sanitize import sentinel_tiled_lookup

    counted, counter = sentinel_tiled_lookup()
    B, I, L, d = 16, 512, 3, 16
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.03)
    orig = kmod.cache_lookup_all_layers_tiled
    kmod.cache_lookup_all_layers_tiled = counted
    try:
        for r in range(4):                      # 4 same-shape rounds
            key = jax.random.PRNGKey(100 + r)
            entries = l2_normalize(jnp.abs(jax.random.normal(key, (L, I, d))))
            table = CacheTable(entries, jnp.ones(I, bool), jnp.ones(L, bool))
            sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                             (B, L, d)))
            from repro.core.semantic_cache import lookup_all_layers
            lookup_all_layers(table, sems, cfg, impl="fused_tiled")
        # one extra distinct shape: a second compile is legitimate
        sems2 = jnp.abs(jax.random.normal(jax.random.PRNGKey(9),
                                          (2 * B, L, d)))
        lookup_all_layers(table, sems2, cfg, impl="fused_tiled")
    finally:
        kmod.cache_lookup_all_layers_tiled = orig
    assert counter.traces == 2, counter.keys
    counter.assert_one_compile_per_shape()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 64), (2, 200, 4, 64),
                                      (1, 384, 2, 128), (2, 64, 1, 96)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, causal):
    q = jax.random.normal(k(5), (B, S, H, hd), jnp.float32)
    kk = jax.random.normal(k(6), (B, S, H, hd), jnp.float32)
    v = jax.random.normal(k(7), (B, S, H, hd), jnp.float32)
    o1 = ops.flash_attention(q, kk, v, causal=causal)
    o2 = ref.flash_attention_ref(q, kk, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_gqa_expansion():
    B, S, H, Hkv, hd = 2, 130, 8, 2, 64
    q = jax.random.normal(k(8), (B, S, H, hd), jnp.float32)
    kk = jax.random.normal(k(9), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(k(10), (B, S, Hkv, hd), jnp.float32)
    o1 = ops.flash_attention_gqa(q, kk, v)
    o2 = ref.flash_attention_ref(q, jnp.repeat(kk, H // Hkv, 2),
                                 jnp.repeat(v, H // Hkv, 2))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16():
    B, S, H, hd = 1, 256, 2, 64
    q = jax.random.normal(k(11), (B, S, H, hd)).astype(jnp.bfloat16)
    kk = jax.random.normal(k(12), (B, S, H, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(k(13), (B, S, H, hd)).astype(jnp.bfloat16)
    o1 = ops.flash_attention(q, kk, v)
    o2 = ref.flash_attention_ref(q.astype(jnp.float32), kk.astype(jnp.float32),
                                 v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(o1, dtype=np.float32),
                               np.asarray(o2), rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# decode attention (+ sharded partial combine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,hd,T", [(2, 4, 4, 64, 128), (3, 8, 2, 64, 300),
                                          (1, 12, 4, 128, 64)])
def test_decode_attention_sweep(B, H, Hkv, hd, T):
    q = jax.random.normal(k(14), (B, H, hd), jnp.float32)
    kc = jax.random.normal(k(15), (B, T, Hkv, hd), jnp.float32)
    vc = jax.random.normal(k(16), (B, T, Hkv, hd), jnp.float32)
    length = jax.random.randint(k(17), (B,), 1, T + 1)
    o1 = ops.decode_attention(q, kc, vc, length)
    rep = H // Hkv
    o2 = ref.decode_attention_ref(q, jnp.repeat(kc, rep, 2),
                                  jnp.repeat(vc, rep, 2), length)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-5)


def test_decode_partial_combine_matches_monolithic():
    B, H, Hkv, hd, T = 2, 8, 2, 64, 256
    q = jax.random.normal(k(18), (B, H, hd), jnp.float32)
    kc = jax.random.normal(k(19), (B, T, Hkv, hd), jnp.float32)
    vc = jax.random.normal(k(20), (B, T, Hkv, hd), jnp.float32)
    length = jnp.array([200, 64], jnp.int32)
    full = ops.decode_attention(q, kc, vc, length)
    accs, ms, ls = [], [], []
    for lo in range(0, T, 64):
        a_, m_, l_ = ops.decode_attention(
            q, kc[:, lo:lo + 64], vc[:, lo:lo + 64],
            jnp.clip(length - lo, 0, 64), return_partial=True)
        accs.append(a_), ms.append(m_), ls.append(l_)
    merged = ops.combine_partials(accs, ms, ls)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [(1, 64, 2, 16, 8, 16),
                                             (2, 256, 4, 32, 16, 64),
                                             (1, 128, 1, 64, 128, 128)])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    x = jax.random.normal(k(21), (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k(22), (B, S, H)))
    a = jnp.exp(-dt * jnp.exp(jax.random.normal(k(23), (H,)) * 0.3))
    Bm = jax.random.normal(k(24), (B, S, N), jnp.float32)
    Cm = jax.random.normal(k(25), (B, S, N), jnp.float32)
    y1 = ops.ssd_scan(x, dt, a, Bm, Cm, chunk=chunk)
    y2 = ref.ssd_scan_ref(x, dt, a, Bm, Cm, chunk=chunk)
    y3 = ref.ssd_sequential_ref(x, dt, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y3),
                               rtol=2e-3, atol=2e-3)


def test_ssd_scan_state_continuity():
    """Splitting a sequence across chunk boundaries must not change outputs —
    proves the inter-chunk recurrence carries the state correctly."""
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = jax.random.normal(k(26), (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k(27), (B, S, H)))
    a = jnp.exp(-dt * 0.5)
    Bm = jax.random.normal(k(28), (B, S, N), jnp.float32)
    Cm = jax.random.normal(k(29), (B, S, N), jnp.float32)
    y_small = ops.ssd_scan(x, dt, a, Bm, Cm, chunk=16)
    y_big = ops.ssd_scan(x, dt, a, Bm, Cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y_small), np.asarray(y_big),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["fused_single", "fused_tiled"])
@pytest.mark.parametrize("quantized", [False, True])
def test_stacked_tables_lookup_matches_per_table(impl, quantized):
    """K stacked client tables in one launch (the round engine's batched
    lookup) == K separate lookups: the table axis only selects operands."""
    from repro.core.semantic_cache import (CacheConfig, CacheTable,
                                           l2_normalize, lookup_all_layers,
                                           quantize_table)
    K, B, I, L, d = 3, 20, 300, 4, 16
    key = jax.random.PRNGKey(41)
    tables = []
    for c in range(K):
        kc = jax.random.fold_in(key, c)
        t = CacheTable(
            l2_normalize(jnp.abs(jax.random.normal(kc, (L, I, d)))),
            jax.random.bernoulli(jax.random.fold_in(kc, 1), 0.7, (I,)),
            jax.random.bernoulli(jax.random.fold_in(kc, 2), 0.8, (L,)))
        tables.append(quantize_table(t) if quantized else t)
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *tables)
    sems = jnp.abs(jax.random.normal(jax.random.fold_in(key, 9), (K, B, L, d)))
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=d, theta=0.02)
    out = lookup_all_layers(stacked, sems, cfg, impl=impl)
    assert out.scores.shape == (K, B, L)
    for c in range(K):
        one = lookup_all_layers(tables[c], sems[c], cfg, impl=impl)
        for f in ("hit", "exit_layer", "pred", "scores"):
            np.testing.assert_array_equal(np.asarray(getattr(out, f))[c],
                                          np.asarray(getattr(one, f)))
