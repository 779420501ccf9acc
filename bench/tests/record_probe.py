#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` checks the reduction
against (run on the chip; writes ``bench/tests/data/probe.xplane.pb``).

Through the benchmark's own ``Tracer`` (a ``bench.window`` span) it runs a jitted ``bench_probe`` (one
matmul) five times, each in a ``bench.step`` span, with a 20 ms
``bench.sleep`` span after each: five programs of that name, and idle time
that falls mostly under ``bench.sleep``."""

import glob
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    from bench.lib import env
    env.prepare(ROOT)
    env.check_device(1)
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_probe(x):
        return (x @ x).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    bench_probe(x).block_until_ready()
    from bench.run import Tracer
    spans = env.Spans(annotate=True)
    out = ROOT / "bench_out" / "probe"
    tracer = Tracer(out)
    tracer.start()
    for _ in range(5):
        with spans.span("bench.step"):
            bench_probe(x).block_until_ready()
        with spans.span("bench.sleep"):
            time.sleep(0.02)
    tracer.stop()
    f, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    dest = ROOT / "bench" / "tests" / "data" / "probe.xplane.pb"
    shutil.copy(f, dest)
    Path(ROOT / "chiprun_out").mkdir(exist_ok=True)
    shutil.copy(f, ROOT / "chiprun_out" / "probe.xplane.pb")
    print(f"wrote {dest} ({dest.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
