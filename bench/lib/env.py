"""Process set-up shared by every cell: compile cache, device check, peaks,
compile counting and host spans.

Import order matters: :func:`prepare` sets the persistent compilation cache
before JAX is imported, so call it first.
"""

from __future__ import annotations

import contextlib
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device not listed is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

# Lowering of a new program: tracing it into MLIR.  A program that is
# already in the in-memory cache fires none of these.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device missing from the peak table."""


def prepare(root: Path = ROOT) -> str:
    """Point JAX's persistent compilation cache at ``<root>/.jax_cache`` (a
    fixed path inside the checkout, so that only a checkout's first run of
    a cell compiles), with every program cached however fast it compiled.
    Must run before ``jax`` is imported.  Returns the directory."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A float32 matmul means float32: the configurations state float32 for
    # the cache tables, taps and class heads, and XLA's default on a TPU
    # would run those dots in one bfloat16 pass.  bfloat16 operands (the
    # backbone) are unaffected.
    jax.config.update("jax_default_matmul_precision", "highest")
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    return path


def check_device(chips: int) -> dict:
    """The device this run measures on: a TPU in the peak table with at
    least ``chips`` devices.  Raises :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {dev.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    if dev.device_kind not in PEAKS:
        raise NoChip(f"device kind {dev.device_kind!r} is not in the peak "
                     f"table {sorted(PEAKS)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, as the backend reports it
    (0 where it reports nothing, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts program lowerings and backend compiles, in total and between
    :meth:`open_window` and :meth:`close_window`."""

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        self.compile_s = 0.0
        self._in_window = False
        self.window_lowered = self.window_compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == LOWER_EVENT:
            self.lowered += 1
            self.window_lowered += self._in_window
        elif event == COMPILE_EVENT:
            self.compiled += 1
            self.compile_s += secs
            self.window_compiled += self._in_window

    def open_window(self) -> None:
        self._in_window = True

    def close_window(self) -> None:
        self._in_window = False


class Spans:
    """Host spans of the benchmark's own calls into the program: with
    ``annotate`` each is a ``TraceAnnotation`` in the profiler's trace, on
    the clock of the device events (``bench/lib/trace.py`` labels idle
    gaps with them); without, a span costs nothing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.annotate:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(name):
            yield
