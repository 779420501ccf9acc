"""cocalint's runtime half: a pytest plugin proving the invariants the
static pass can only approximate.

Three sanitizers (docs/analysis.md has the full catalog):

* **Transfer guard** — ``jax.transfer_guard("disallow")`` scopes around the
  jitted ``round_step`` / serving-tick calls.  Explicit, bundled transfers
  (``jax.device_get`` / ``jax.device_put`` / ``jnp.asarray``) stay legal;
  an *implicit* transfer — a stray NumPy array flowing into a jit boundary
  — raises.  Tests opt in with ``@pytest.mark.no_implicit_transfers`` (the
  whole test runs guarded) or the :func:`no_implicit_transfers` context
  manager (guard exactly the hot calls).

* **Recompilation sentinel** — :func:`counted_jit` re-jits a function with
  a trace counter that records one signature key per trace (dynamic-leaf
  shapes/dtypes + tree structure + static kwargs).  ``counter.traces ==
  counter.distinct`` is the invariant "exactly one compile per distinct
  shape"; a retrace storm shows up as ``traces > distinct``.
  :func:`sentinel_round_step` / :func:`sentinel_batched_lookup` pre-wire
  the two production hot paths for monkeypatching, and
  :func:`sentinel_table_cut` / :func:`sentinel_round_stack` the round's
  two preparation calls.

* **Checkify debug mode** — :func:`checked_lookup` runs the fused Pallas
  cache lookup under ``checkify`` NaN/OOB checks; ``pytest
  --cocalint-debug`` reroutes every ServingSession tick's lookup through
  it for a whole run (slow; a chaos-debugging aid, not a default gate).

Loaded via ``pytest_plugins`` in the rootdir ``conftest.py``.
"""

from __future__ import annotations

import contextlib
import functools
import inspect

import jax
import jax.numpy as jnp

try:
    import pytest
except ImportError:                                    # CLI-only usage
    pytest = None


# ---------------------------------------------------------------------------
# Transfer guard
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def no_implicit_transfers():
    """Scope in which any implicit host<->device transfer raises.

    Explicit transfers (``jax.device_get`` / ``device_put`` /
    ``jnp.asarray``) remain legal — the engine's contract is *one bundled
    explicit* ``device_get`` per round/tick, not zero transfers.
    """
    with jax.transfer_guard("disallow"):
        yield


# ---------------------------------------------------------------------------
# Recompilation sentinel
# ---------------------------------------------------------------------------


class TraceCounter:
    """Counts traces of a :func:`counted_jit`-wrapped function.

    ``traces``   — times the Python body ran (== compiles, jit caches aside).
    ``keys``     — one signature key per trace: (leaf shapes/dtypes,
                   tree structure, static kwargs).
    ``distinct`` — distinct signature keys seen.

    The sanitizer invariant is ``traces == distinct``: every compile is
    explained by a genuinely new signature.  A shape-unstable hot loop
    (or an unhashed static leaking into the trace) shows up as
    ``traces > distinct`` or as ``distinct`` exploding with the loop.
    """

    def __init__(self) -> None:
        self.traces = 0
        self.keys: list = []

    @property
    def distinct(self) -> int:
        return len(set(self.keys))

    def assert_one_compile_per_shape(self) -> None:
        assert self.traces == self.distinct, (
            f"retrace storm: {self.traces} traces for only "
            f"{self.distinct} distinct call signatures — keys={self.keys}")


def counted_jit(fun, *, static_argnames=(), **jit_kwargs):
    """``(jitted_fun, TraceCounter)`` — ``fun`` re-jitted with a sentinel.

    Monkeypatch the production binding with ``jitted_fun`` and pin
    ``counter.traces`` after driving the real code path.
    """
    counter = TraceCounter()
    static = frozenset(static_argnames)
    sig = inspect.signature(fun)

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        counter.traces += 1
        # Bind by name so a static passed positionally still lands in the
        # static half of the key (jax.jit matches static_argnames the same
        # way) — otherwise two Θ-distinct configs collapse into one key
        # and a legitimate retrace reads as a storm.
        bound = sig.bind(*args, **kwargs)
        dyn = {k: v for k, v in bound.arguments.items() if k not in static}
        leaves, treedef = jax.tree_util.tree_flatten(dyn)
        key = (
            tuple((getattr(leaf, "shape", None),
                   str(getattr(leaf, "dtype", type(leaf).__name__)))
                  for leaf in leaves),
            str(treedef),
            tuple(sorted((k, repr(v))
                         for k, v in bound.arguments.items() if k in static)),
        )
        counter.keys.append(key)
        return fun(*args, **kwargs)

    return (jax.jit(wrapper, static_argnames=tuple(static_argnames),
                    **jit_kwargs),
            counter)


def sentinel_round_step():
    """Counted drop-in for ``repro.core.engine.round_step`` — monkeypatch
    ``repro.core.engine.round_step`` with the returned function."""
    from repro.core import engine as engine_mod
    raw = engine_mod.round_step.__wrapped__
    return counted_jit(raw, static_argnames=(
        "cfg", "absorb", "scfg", "cm", "global_updates", "deadline",
        "mesh"))


def sentinel_table_cut():
    """Counted drop-in for ``repro.core.engine.allocate_subtables`` — the
    round's one batched table cut; monkeypatch the ``engine`` binding."""
    from repro.core import semantic_cache
    raw = semantic_cache.allocate_subtables.__wrapped__
    return counted_jit(raw, static_argnames=("entry_dtype", "stacked"))


def sentinel_round_stack():
    """Counted drop-in for ``repro.core.engine._stack`` — the round's one
    stack of the clients' taps and logits."""
    from repro.core import engine as engine_mod
    return counted_jit(engine_mod._stack.__wrapped__)


def sentinel_batched_lookup():
    """Counted drop-in for ``repro.serving.loop._batched_lookup`` — the
    serving tick's one jit boundary."""
    from repro.serving import loop as loop_mod
    raw = loop_mod._batched_lookup.__wrapped__
    return counted_jit(raw, static_argnames=("cfg",))


def sentinel_tiled_lookup():
    """Counted drop-in for the double-buffered class-tiled cache lookup
    (``repro.kernels.cache_lookup.cache_lookup_all_layers_tiled``) — the
    manual-DMA pipeline must trace once per table/batch shape, not once per
    round; monkeypatch the ``cache_lookup`` module binding."""
    from repro.kernels import cache_lookup as kmod
    raw = kmod.cache_lookup_all_layers_tiled.__wrapped__
    return counted_jit(raw, static_argnames=("alpha", "i_block", "interpret"))


# ---------------------------------------------------------------------------
# Checkify debug mode
# ---------------------------------------------------------------------------


def _checkify_errors(kernel: bool):
    from jax.experimental import checkify
    if kernel:
        # checkify's out-of-bounds grid check over a pallas_call (JAX 0.9)
        # seeds its loop with float32 blocks and fails to trace for a
        # kernel with int32 outputs, as the fused lookups have.  Through a
        # kernel the index checks are therefore the explicit output-range
        # checks in _checked_lookup_jit (user checks).
        return checkify.float_checks | checkify.user_checks
    return checkify.float_checks | checkify.index_checks


@functools.cache
def _checked_lookup_jit(impl: str):
    from jax.experimental import checkify

    from repro.core.semantic_cache import lookup_all_layers

    kernel = impl != "ref" and (impl != "auto"
                                or jax.default_backend() == "tpu")

    def fn(table, sems, cfg):
        out = lookup_all_layers(table, sems, cfg, impl=impl)
        if kernel:
            L, I = cfg.num_layers, cfg.num_classes
            checkify.check(jnp.all((out.exit_layer >= 0)
                                   & (out.exit_layer <= L)),
                           "lookup exit layer out of range [0, L]")
            checkify.check(jnp.all((out.pred >= 0) & (out.pred < I)),
                           "lookup prediction out of range [0, I)")
        return out

    return jax.jit(checkify.checkify(fn, errors=_checkify_errors(kernel)),
                   static_argnames=("cfg",))


def checked_lookup(table, sems, cfg, *, impl: str = "fused"):
    """The fused cache lookup under checkify NaN/OOB checks.

    Raises ``JaxRuntimeError`` on the first NaN/inf produced anywhere
    inside the lookup (Pallas kernels run in interpret mode on CPU, where
    checkify sees through them), and on an out-of-bounds index: checkify's
    own index checks on the reference path, and range checks on the
    exit layers and predictions a kernel returns.  Returns the usual
    ``LookupResult``.
    """
    err, out = _checked_lookup_jit(impl)(table, sems, cfg=cfg)
    err.throw()
    return out


# ---------------------------------------------------------------------------
# pytest wiring
# ---------------------------------------------------------------------------

if pytest is not None:

    def pytest_addoption(parser):
        parser.addoption(
            "--cocalint-debug", action="store_true", default=False,
            help="route every ServingSession lookup through checkify "
                 "NaN/OOB checks (slow; chaos-debugging aid)")

    def pytest_configure(config):
        config.addinivalue_line(
            "markers",
            "no_implicit_transfers: run the whole test under "
            "jax.transfer_guard('disallow') — any implicit host<->device "
            "transfer fails the test")

    @pytest.fixture(autouse=True)
    def _cocalint_transfer_guard(request):
        if request.node.get_closest_marker("no_implicit_transfers"):
            with no_implicit_transfers():
                yield
        else:
            yield

    @pytest.fixture
    def recompile_sentinel():
        """Factory fixture: ``recompile_sentinel(fun, static_argnames=...)``
        returns ``(jitted, TraceCounter)``."""
        return counted_jit

    @pytest.fixture
    def cocalint_debug(request) -> bool:
        return bool(request.config.getoption("--cocalint-debug"))

    @pytest.fixture(autouse=True)
    def _cocalint_checkify_mode(request, monkeypatch):
        """``--cocalint-debug``: reroute the serving tick's lookup through
        the checkified path for every test in the run."""
        if not request.config.getoption("--cocalint-debug"):
            yield
            return
        from repro.serving import loop as loop_mod

        def checked(table, sems, cfg):
            # the session's lookup dispatches impl="auto"; mirror it here
            return checked_lookup(table, sems, cfg, impl="auto")

        monkeypatch.setattr(loop_mod, "_batched_lookup", checked)
        yield
