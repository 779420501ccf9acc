"""Class-sharded global cache (server side): the Eq.-4/5 merge and the
round driver must be bit-identical with the (L, I, d) table split over a
device mesh — the only collective is the entries all-gather at subtable
allocation (see repro/distributed/sharding.py, "CoCa server global cache")."""

import pytest


@pytest.mark.slow
def test_global_update_sharded_parity():
    from tests.conftest import run_multidevice
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.semantic_cache import l2_normalize
from repro.core.server import ServerConfig, ServerState, global_update_body
from repro.core.client import ClientUpload
from repro.distributed.sharding import (gather_cache, server_cache_specs,
                                        shard_server_state)

mesh = jax.make_mesh((4,), ("data",))
I, L, d = 64, 6, 32
k = jax.random.PRNGKey(0)
srv = ServerState(
    entries=l2_normalize(jax.random.normal(k, (L, I, d))),
    phi_global=jnp.abs(jax.random.normal(jax.random.fold_in(k, 1), (I,))) * 10,
    r_est=jnp.linspace(0.1, 0.9, L),
    upsilon=jnp.linspace(30, 5, L))
up = ClientUpload(
    tau=jnp.zeros(I, jnp.int32),
    phi=jax.random.randint(jax.random.fold_in(k, 2), (I,), 0, 5),
    u=jax.random.normal(jax.random.fold_in(k, 3), (L, I, d)),
    u_touched=jax.random.bernoulli(jax.random.fold_in(k, 4), 0.3, (L, I)),
    hit_counts=jax.random.randint(jax.random.fold_in(k, 5), (L,), 0, 10),
    lookup_counts=jax.random.randint(jax.random.fold_in(k, 6), (L,), 0, 20))
scfg = ServerConfig()
ref = global_update_body(srv, up, scfg)

srv_sh = shard_server_state(srv, mesh)
assert "data" in str(srv_sh.entries.sharding.spec), srv_sh.entries.sharding
out = jax.jit(lambda s, u: global_update_body(s, u, scfg))(srv_sh, up)
# the merge is elementwise in I: the class axis must STAY sharded
assert "data" in str(out.entries.sharding.spec), out.entries.sharding
for name in ("entries", "phi_global", "r_est"):
    np.testing.assert_allclose(np.asarray(getattr(out, name)),
                               np.asarray(getattr(ref, name)),
                               rtol=1e-6, atol=1e-6)
g = gather_cache(out.entries, mesh)
assert g.sharding.spec == jax.sharding.PartitionSpec(None, None, None)
print("GLOBAL UPDATE SHARDED PARITY OK")
""", devices=4)


@pytest.mark.slow
def test_run_simulation_sharded_parity():
    from tests.conftest import run_multidevice
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (CacheConfig, SimulationConfig, bootstrap_server,
                        calibrate, run_simulation)
from repro.data import (StreamConfig, dirichlet_client_priors,
                        make_client_context, make_tap_model,
                        perturb_tap_model, sample_class_sequence,
                        synthesize_taps)

I, L, D, F = 16, 4, 16, 40
scfg = StreamConfig(num_classes=I, num_layers=L, sem_dim=D)
tm = make_tap_model(jax.random.PRNGKey(0), scfg)
tm_cal = perturb_tap_model(jax.random.PRNGKey(42), tm, 0.35)
cm = calibrate(np.full(L + 1, 5.0), np.full(L, D), head_cost=1.0)
shared = np.tile(np.arange(I), 10)
def tap_shared(lab):
    return synthesize_taps(jax.random.PRNGKey(1), tm_cal, jnp.asarray(lab), scfg)

cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=D, theta=0.1)
sim = SimulationConfig(cache=cfg, round_frames=F, mem_budget=8_000.0)
rng = np.random.default_rng(0)
clients, rounds = 2, 3
priors = dirichlet_client_priors(rng, clients, I, 2.0)
labels = np.stack([np.stack([sample_class_sequence(rng, priors[k], F, 0.9)
                             for k in range(clients)]) for _ in range(rounds)])
ctxs = [make_client_context(jax.random.PRNGKey(100 + k), scfg)
        for k in range(clients)]
def mk_tapfn():
    ctr = [0]
    def tap_fn(r, k, lab):
        ctr[0] += 1
        return synthesize_taps(jax.random.PRNGKey(1000 + ctr[0]), tm,
                               jnp.asarray(lab), scfg, context=ctxs[k])
    return tap_fn

server = bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared, shared, cm)
res_plain = run_simulation(sim, server, mk_tapfn(), labels, cm, rounds, clients)

mesh = jax.make_mesh((4,), ("data",))
server_sh = bootstrap_server(jax.random.PRNGKey(0), sim, tap_shared, shared,
                             cm, mesh=mesh)
assert "data" in str(server_sh.entries.sharding.spec)
res_mesh = run_simulation(sim, server_sh, mk_tapfn(), labels, cm, rounds,
                          clients, mesh=mesh)

np.testing.assert_allclose(res_mesh.per_round_latency,
                           res_plain.per_round_latency, rtol=1e-5)
np.testing.assert_allclose(res_mesh.per_round_accuracy,
                           res_plain.per_round_accuracy, rtol=1e-5)
np.testing.assert_array_equal(res_mesh.exit_histogram,
                              res_plain.exit_histogram)
np.testing.assert_allclose(np.asarray(res_mesh.server.entries),
                           np.asarray(res_plain.server.entries),
                           rtol=1e-5, atol=1e-6)
print("SHARDED SIMULATION PARITY OK")
""", devices=4)


def test_profile_initial_cache_sharded():
    from tests.conftest import run_multidevice
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.server import profile_initial_cache

mesh = jax.make_mesh((4,), ("data",))
N, L, I, d = 120, 4, 32, 16
k = jax.random.PRNGKey(7)
sems = jax.random.normal(k, (N, L, d))
labels = jax.random.randint(jax.random.fold_in(k, 1), (N,), 0, I)
e_ref, phi_ref = profile_initial_cache(sems, labels, I)
e_sh, phi_sh = profile_initial_cache(sems, labels, I, mesh=mesh)
assert "data" in str(e_sh.sharding.spec), e_sh.sharding
assert "data" in str(phi_sh.sharding.spec), phi_sh.sharding
np.testing.assert_allclose(np.asarray(e_sh), np.asarray(e_ref),
                           rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(np.asarray(phi_sh), np.asarray(phi_ref))
print("PROFILE SHARDED OK")
""", devices=4)


def test_fused_merge_class_sharded_rounds_match_one_device():
    """The Pallas merge under a class-sharded ServerState runs per class
    shard (``shard_map`` over the class axis): CocaCluster rounds on a
    4-device mesh match the same rounds on one device, the table stays
    split across rounds, and the compiled round carries no all-gather of
    the (L, I, d) table.  Kernels run interpreted on the virtual CPU
    devices; chip_smoke.py --chips 4 makes the same comparison on chips."""
    from tests.conftest import run_multidevice
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (CacheConfig, CocaCluster, FrameBatch,
                        SimulationConfig, calibrate)
from repro.core import engine as engine_mod
from repro.core.server import ServerConfig
from repro.data import (StreamConfig, make_tap_model, perturb_tap_model,
                        synthesize_taps)

I, L, D, F, K = 64, 4, 16, 24, 3
scfg = StreamConfig(num_classes=I, num_layers=L, sem_dim=D)
tm = make_tap_model(jax.random.PRNGKey(0), scfg)
tm_cal = perturb_tap_model(jax.random.PRNGKey(42), tm, 0.35)
cm = calibrate(np.full(L + 1, 5.0), np.full(L, D), head_cost=1.0)
shared = np.tile(np.arange(I), 4)
cal = synthesize_taps(jax.random.PRNGKey(1), tm_cal, jnp.asarray(shared), scfg)
sim = SimulationConfig(
    cache=CacheConfig(num_classes=I, num_layers=L, sem_dim=D, theta=0.1),
    server=ServerConfig(merge_impl="fused"), round_frames=F,
    mem_budget=16_000.0)
rng = np.random.default_rng(np.random.SeedSequence((3,)))
rounds = [[rng.integers(0, I, F) for _ in range(K)] for _ in range(2)]

compiled = []
orig = engine_mod.round_step
def spy(*args, **kw):
    compiled.append(orig.lower(*args, **kw).compile().as_text())
    return orig(*args, **kw)
engine_mod.round_step = spy

def run(mesh):
    cl = CocaCluster(sim, cm, num_clients=K, mesh=mesh)
    cl.bootstrap(jax.random.PRNGKey(0), cal, shared)
    metrics = []
    for r, labs in enumerate(rounds):
        metrics.append(cl.step([FrameBatch(*synthesize_taps(
            jax.random.PRNGKey(100 * r + k), tm, jnp.asarray(lab), scfg),
            labels=lab) for k, lab in enumerate(labs)]))
    return cl, metrics

one, m1 = run(None)
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
sh, m4 = run(mesh)
assert "data" in str(sh.server.entries.sharding.spec), sh.server.entries.sharding
for a, b in zip(m1, m4):
    for f in ("pred", "hit", "exit_layer"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
np.testing.assert_array_equal(np.asarray(sh.server.phi_global),
                              np.asarray(one.server.phi_global))
np.testing.assert_allclose(np.asarray(sh.server.entries),
                           np.asarray(one.server.entries), rtol=1e-6,
                           atol=1e-6)
table = f"f32[{L},{I},{D}]"
gathers = [ln for ln in compiled[-1].splitlines()
           if "all-gather" in ln and table in ln]
assert not gathers, gathers
print("FUSED MERGE SHARDED PARITY OK")
""", devices=4)
