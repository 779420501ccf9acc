"""Compile-only guards: every Pallas kernel of the serving and round paths,
compiled by the TPU compiler for a described (not attached) TPU v5e at the
widths the chip sees.

Interpret-mode parity (tests/test_kernels.py, tests/test_merge_kernel.py)
cannot catch what only Mosaic refuses: unaligned blocks, scatters, scoped
VMEM overflow, compile times that grow with the table.  These tests run
nothing — they hand shapes to ``.lower(...).compile()`` — so they need no
chip.  The topology is described inside a fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.

Widths: coca-ast taps (L=12 cache layers, sem_dim d=256) at the kernel's
128-row batch tile; the single-pass lookup at I=50 (ESC-50) and at the VMEM
budget's ceilings (I=768 float32, I=2,944 int8); the class-tiled lookup at
I=16,384 and 65,536; the round merge at the paper's K=5 clients, I=50, and
at K=64, I=4,096; the whole round step at the paper's deployment, on one
chip and on a 4-chip mesh.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import common
from repro.kernels.cache_lookup import (cache_lookup_all_layers,
                                        cache_lookup_all_layers_tiled)
from repro.kernels.cache_merge import cache_merge_round

B, L, D = 128, 12, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described device's executable is written to the persistent cache
    # but cannot be read back without a chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(lowered):
    t0 = time.perf_counter()
    text = lowered.compile().as_text()
    secs = time.perf_counter() - t0
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text, secs


def _lookup(kernel, I, quantized, sh, **kw):
    return kernel.lower(
        _spec((B, L, D), jnp.float32, sh),
        _spec((L, I, D), jnp.int8 if quantized else jnp.float32, sh),
        _spec((I,), jnp.bool_, sh), _spec((L,), jnp.bool_, sh),
        _spec((L,), jnp.float32, sh), alpha=0.5,
        entry_scale=_spec((L, I), jnp.bfloat16, sh) if quantized else None,
        interpret=False, **kw)


@pytest.mark.parametrize("I,dtype", [(50, "float32"), (50, "int8"),
                                     (768, "float32"), (2944, "int8")])
def test_single_pass_lookup_compiles(one_chip, I, dtype):
    # I=768 / 2,944 are the largest tables the VMEM budget sends here.
    assert common.single_pass_fits(L, I, D, entry_dtype=dtype)
    _, secs = _compile(_lookup(cache_lookup_all_layers, I, dtype == "int8",
                               one_chip))
    # The class-tile loop is rolled: compile time does not grow with I
    # (the unrolled loop took minutes at I=768).
    assert secs < 60, f"single-pass compile took {secs:.0f} s at I={I}"


def test_single_pass_ceilings_are_the_budget_edge():
    assert not common.single_pass_fits(L, 768 + common.I_TILE, D)
    assert not common.single_pass_fits(L, 2944 + common.I_TILE, D,
                                       entry_dtype="int8")


@pytest.mark.parametrize("I", [16_384, 65_536])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_tiled_lookup_compiles(one_chip, I, dtype):
    # Default class block: the budget model's pick must fit the chip's
    # scoped VMEM (its plan once booked one of the two DMA slots only).
    assert not common.single_pass_fits(L, I, D, entry_dtype=dtype)
    _compile(_lookup(cache_lookup_all_layers_tiled, I, dtype == "int8",
                     one_chip))


def _merge_args(K, I, sh_entries, sh_phi, sh_u, sh_k_i, sh_k_l_i, sh_k):
    return (_spec((L, I, D), jnp.float32, sh_entries),
            _spec((I,), jnp.float32, sh_phi),
            _spec((K, L, I, D), jnp.float32, sh_u),
            _spec((K, I), jnp.int32, sh_k_i),
            _spec((K, L, I), jnp.bool_, sh_k_l_i),
            _spec((K,), jnp.bool_, sh_k))


@pytest.mark.parametrize("K,I", [(5, 50), (64, 4096)])
def test_merge_compiles(one_chip, K, I):
    args = _merge_args(K, I, *([one_chip] * 6))
    _compile(cache_merge_round.lower(*args, gamma=0.99, interpret=False))


def test_class_sharded_merge_compiles_without_gathers(topo, monkeypatch):
    """merge_round on a 4-chip class-sharded ServerState: the kernel runs
    per class shard, so the compiled round merge holds the Mosaic kernel and
    no all-gather of the (L, I, d) table."""
    from jax.sharding import Mesh
    import numpy as np

    from repro.core.client import ClientUpload
    from repro.core.server import ServerConfig, ServerState, merge_round_jit

    # This process's backend is the CPU; steer the kernel to compile for
    # the described chip instead of being interpreted.
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    K, I = 5, 4096

    def sh(*spec):
        return NamedSharding(mesh, P(*spec))

    entries, phi, u, phik, touched, include = _merge_args(
        K, I, sh(None, "data", None), sh("data"), sh(), sh(), sh(), sh())
    vec = _spec((L,), jnp.float32, sh())
    server = ServerState(entries=entries, phi_global=phi, r_est=vec,
                         upsilon=vec)
    uploads = ClientUpload(
        tau=_spec((K, I), jnp.int32, sh()), phi=phik, u=u,
        u_touched=touched, hit_counts=_spec((K, L), jnp.int32, sh()),
        lookup_counts=_spec((K, L), jnp.int32, sh()))
    text, _ = _compile(merge_round_jit.lower(
        server, uploads, include, scfg=ServerConfig(merge_impl="fused"),
        mesh=mesh))
    table = f"f32[{L},{I},{D}]"
    gathers = [ln for ln in text.splitlines()
               if "all-gather" in ln and table in ln]
    assert not gathers, gathers


@pytest.mark.parametrize("chips,I", [(1, 50), (4, 64), (4, 50)])
def test_round_step_compiles(topo, monkeypatch, chips, I):
    """The collaborative round at the paper's deployment (5 clients, 150
    frames per round, coca-ast taps) holds the lookup and merge kernels on
    one chip and on a 4-chip mesh — class-sharded where the mesh divides I,
    replicated where it does not — and never all-gathers the table."""
    from jax.sharding import AxisType, Mesh
    import numpy as np

    from repro.core import CacheConfig, calibrate
    from repro.core.client import AbsorptionConfig, init_client
    from repro.core.engine import round_step
    from repro.core.semantic_cache import CacheTable
    from repro.core.server import ServerConfig, ServerState

    # This process's backend is the CPU; steer the dispatch to the kernels
    # and compile them for the described chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    K, F = 5, 150
    mesh = None
    rep = cls = SingleDeviceSharding(topo.devices[0])
    if chips == 4:
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("model",),
                    axis_types=(AxisType.Auto,))
        rep = NamedSharding(mesh, P())
        cls = NamedSharding(mesh, P("model")) if I % 4 == 0 else rep
    cfg = CacheConfig(num_classes=I, num_layers=L, sem_dim=D, theta=0.05)
    states = jax.tree_util.tree_map(
        lambda x: _spec((K,) + x.shape, x.dtype, rep),
        jax.eval_shape(lambda: init_client(cfg)))
    tables = CacheTable(_spec((K, L, I, D), jnp.float32, rep),
                        _spec((K, I), jnp.bool_, rep),
                        _spec((K, L), jnp.bool_, rep))
    ent_sh = (NamedSharding(mesh, P(None, "model")) if cls is not rep
              else rep)
    server = ServerState(_spec((L, I, D), jnp.float32, ent_sh),
                         _spec((I,), jnp.float32, cls),
                         _spec((L,), jnp.float32, rep),
                         _spec((L,), jnp.float32, rep))
    text, _ = _compile(round_step.lower(
        states, tables, _spec((K, F, L, D), jnp.float32, rep),
        _spec((K, F, I), jnp.float32, rep), server, cfg=cfg,
        absorb=AbsorptionConfig(), scfg=ServerConfig(),
        cm=calibrate(np.full(L + 1, 5.0), np.full(L, D), head_cost=1.0),
        global_updates=True, deadline=None, mesh=mesh))
    assert text.count("tpu_custom_call") >= 2      # lookup + merge
    table = f"f32[{L},{I},{D}]"
    assert not [ln for ln in text.splitlines()
                if "all-gather" in ln and table in ln]


# ---------------------------------------------------------------------------
# granite-4.0-h-small's layer kernels at its published widths: the SSD scan
# (128 heads of 64, d_state 128, chunk 256) and the held-expert layer (18 of
# 72 experts of width 768, top-10, shared expert 1536), on one 8-row call of
# 512 positions, with float32 matmuls at ``highest`` as the benchmark runs
# them (a precision a bfloat16 kernel matmul must not inherit).
# ---------------------------------------------------------------------------

def test_ssd_scan_compiles_at_granite_widths(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    B_, S, H, P_, N = 8, 512, 128, 64, 128
    with jax.default_matmul_precision("highest"):
        text, _ = _compile(jax.jit(
            lambda *a: ssd_scan(*a, chunk=256, interpret=False)).lower(
            _spec((B_, S, H, P_), jnp.bfloat16, one_chip),
            _spec((B_, S, H), jnp.float32, one_chip),
            _spec((B_, S, H), jnp.float32, one_chip),
            _spec((B_, S, N), jnp.bfloat16, one_chip),
            _spec((B_, S, N), jnp.bfloat16, one_chip)))
    assert "%ssd_scan" in text


def test_held_expert_layer_compiles_at_granite_widths(one_chip):
    from repro.models import moe
    from repro.models.config import ModelConfig
    cfg = ModelConfig(
        name="granite-moe", family="hybrid", num_layers=1, d_model=4096,
        num_heads=32, kv_heads=8, d_ff=0, vocab_size=100352,
        moe={"num_experts": 72, "top_k": 10, "d_expert": 768,
             "d_shared": 1536, "held": (0, 18)})
    params = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, one_chip),
        jax.eval_shape(lambda: moe.moe_init(jax.random.PRNGKey(0), cfg)))
    with jax.default_matmul_precision("highest"):
        text, _ = _compile(jax.jit(
            lambda p, x: moe.moe_fwd(p, x, cfg).y).lower(
            params, _spec((8, 512, 4096), jnp.bfloat16, one_chip)))
    assert text.count("%ragged-dot") >= 3


# ---------------------------------------------------------------------------
# Which SSD a Mamba-2 layer runs on a TPU: prefill (the serving path) runs
# the Pallas scan; a training step keeps the differentiable jnp scan, since
# the kernel has no backward pass.  The backend is reported as a TPU, as on
# the chip, so the layer makes the choice it makes there.
# ---------------------------------------------------------------------------

def _mamba_cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="mamba-tiny", family="ssm", num_layers=2, d_model=256,
        num_heads=4, kv_heads=4, d_ff=0, vocab_size=512,
        ssm={"d_state": 128, "d_conv": 4, "expand": 2, "head_dim": 64,
             "chunk": 128, "conv_bias": True})


def _mamba_params(cfg, sharding):
    from repro.models import init_params
    return jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, sharding),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))


def test_mamba_prefill_runs_the_ssd_kernel(one_chip, monkeypatch):
    from repro.models import prefill
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _mamba_cfg()
    text, _ = _compile(jax.jit(lambda p, t: prefill(p, {"tokens": t}, cfg)[0])
                       .lower(_mamba_params(cfg, one_chip),
                              _spec((2, 256), jnp.int32, one_chip)))
    assert "%ssd_scan" in text


def test_mamba_train_step_compiles_with_the_jnp_scan(one_chip, monkeypatch):
    from repro.training.train_step import make_loss_fn
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _mamba_cfg()
    grad = jax.jit(jax.grad(lambda p, b: make_loss_fn(cfg)(p, b)[0]))
    tok = _spec((2, 256), jnp.int32, one_chip)
    text = grad.lower(_mamba_params(cfg, one_chip),
                      {"tokens": tok, "labels": tok}).compile().as_text()
    assert "%ssd_scan" not in text
