#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file (``configs[].file``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the driver in
``bench/drivers/``), the cell's own parameters and limits
(``bench/workloads/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``).

Set-up (weights, bootstrap, warm-up of every shape) is timed from process
start to the first timed request; then the window runs for ``--seconds``;
then the program's state is freed and the served results are compared with
the plain reference.  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, are the last lines of
standard error and the result's last key.  Without a TPU the run exits 3
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class Context:
    """What a cell's driver and a metric reader are given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Tracer:
    """The profiler around the window, in a ``bench.window`` host span,
    without the Python tracer or HLO protos."""

    def __init__(self, path: Path):
        self.path, self.on = path, False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path), profiler_options=opts)
        # the device tracer can miss what runs in its first moments
        jax.block_until_ready(jax.numpy.zeros(8) + 1)
        time.sleep(0.05)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.on = True

    def stop(self) -> None:
        if self.on:
            import jax
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "bench": bench, "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json")
            .read_text()),
        "workload": json.loads(
            (root / "bench" / "workloads" / f"{name}.json").read_text()),
    }


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metric(name: str, ctx, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def judge(checks: dict, limits: dict) -> bool:
    """``correct``: every number compared is finite and within its limit."""
    return all(math.isfinite(v) and v <= limits[k] for k, v in checks.items())


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             device_check: bool = True, loaded: dict | None = None,
             root: Path = ROOT) -> dict:
    """One run of cell ``name``; returns the result object."""
    from bench.lib import env
    env.prepare(root)
    import jax

    if device_check:
        device = env.check_device(1)
    else:                                 # the harness's own tests
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    ld = loaded or load_cell(name, root)
    if device_check and ld["cell"]["chips"] > device["count"]:
        raise env.NoChip(f"cell needs {ld['cell']['chips']} chips, "
                         f"found {device['count']}")
    from bench.lib import counts, trace as trace_lib
    counter = env.CompileCounter()
    spans = env.Spans(annotate=trace)
    if trace:      # a traced run may trace a shorter window (the cell says)
        seconds = min(seconds, ld["workload"].get("trace_seconds", seconds))
    ctx = Context(name=name, seed=seed, seconds=seconds, spans=spans,
                  config=ld["config"], traffic=ld["traffic"],
                  workload=ld["workload"], counts=counts)
    driver = importlib.import_module(f"bench.drivers.{ld['traffic']['kind']}")
    cell = driver.Cell(ctx)
    cell.setup()
    jax.effects_barrier()
    gc.collect()
    gc.freeze()          # what set-up made is not rescanned in the window
    setup_s = time.perf_counter() - T_START
    print(f"[setup] setup_s={setup_s:.3f} programs_lowered={counter.lowered}"
          f" backend_compiles={counter.compiled} "
          f"compile_s={counter.compile_s:.3f}", flush=True)

    trace_dir = root / "bench_out" / "trace"
    tracer = Tracer(trace_dir)
    if trace:
        tracer.start()
    counter.open_window()
    cell.window(seconds)
    counter.close_window()
    tracer.stop()
    print(f"[window] programs_lowered_in_window={counter.window_lowered} "
          f"backend_compiles_in_window={counter.window_compiled}",
          flush=True)

    device["memory_peak_bytes"] = env.memory_peak_bytes()
    e2e = cell.metrics()
    e2e["setup_s"] = setup_s
    counters = cell.counters()
    cell.release()
    checks = cell.check()
    limits = ld["workload"]["limits"]
    correct = judge(checks, limits)

    bench = ld["bench"]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    metrics, breakdown = {}, None
    if trace:
        tr = trace_lib.read(str(trace_dir))
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = tr.window_s
        rctx = Context(trace=tr, counters=counters, seconds=seconds,
                       peaks=env.PEAKS[device["kind"]] if device_check
                       else env.PEAKS["TPU v5 lite"],
                       lib=trace_lib, counts=counts)
        for m in bench["per_layer"]:
            if applies(m, name):
                v = read_metric(m["name"], rctx, root)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        breakdown = {"device_ops": trace_lib.top_ops(tr),
                     "idle_gaps": trace_lib.idle_gaps(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in bench["end_to_end"]:
            if applies(m, name) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": units[m["name"]]}
    out = {"correct": bool(correct), "attempted": int(cell.attempted),
           "failed": int(cell.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": limits[k]}
                     for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from bench.lib import env
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except env.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
