#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell's configuration and traffic mix; each is a
file of its own under ``bench/configs`` and ``bench/traffic``, and the mix's
``kind`` names the driver in ``bench/drivers``. With ``--trace 0`` the last
line of standard output is the cell's end-to-end metrics; with
``--trace 1`` a profiler records the window and the line carries the
cell's per-layer metrics, each read by ``bench/metrics/<name>.py``. The
line also says whether what the window produced matched the plain
reference (``correct``), and the numbers compared with their limits
(``bench/limits/<cell>.json``) come last in it and on standard error.

It refuses to run where JAX finds no TPU, fewer chips than the cell asks
for, or a device kind that ``bench/lib/peaks.json`` does not list.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Env:
    """What a driver asks of the harness: the set-up clock, the compile
    counter, the harness's trace spans, the profiler and the device's
    memory peak."""

    def __init__(self, device, trace: bool, t0: float):
        import jax

        self.device = device
        self.tracing = trace
        self.t0 = t0
        self.setup_s = None
        self.compiles = 0          # programs compiled, not loaded
        self.cache_loads = 0       # programs loaded from the disk cache
        self.peak_bytes = None
        self.trace = None
        self._dir = None
        self._window = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        # fired around every compile request, a hit in the disk cache too
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.compiles -= 1
            self.cache_loads += 1

    log = staticmethod(log)

    @contextlib.contextmanager
    def phase(self, name):
        c0, l0 = self.compiles, self.cache_loads
        yield
        if name == "setup":
            self.setup_s = time.perf_counter() - self.t0
        log(f"phase={name} end_s={time.perf_counter() - self.t0!r} "
            f"compiles={self.compiles - c0} "
            f"cache_loads={self.cache_loads - l0}")

    def span(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_start(self):
        if not self.tracing:
            return
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir)
        self._window = jax.profiler.TraceAnnotation("bench_window")
        self._window.__enter__()

    def trace_stop(self):
        if not self.tracing or self._window is None:
            return
        import jax

        from bench.lib import trace as T

        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(self._dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            prof = jax.profiler.ProfileData.from_file(str(files[-1]))
            self.trace = T.load(prof)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def memory_peak(self):
        stats = self.device.memory_stats() or {}
        self.peak_bytes = stats.get("peak_bytes_in_use")


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, section: str) -> list:
    """Metrics of ``section`` that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def setup_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set, else ``.jax_cache/`` at the root of the checkout, with every
    program kept so that only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        CACHE_DIR.mkdir(exist_ok=True)
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge(readings: dict, limits: dict):
    """``(correct, checks)``: every reading at or under its limit."""
    checks, ok = {}, True
    for name, value in readings.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not value <= limit:
            ok = False
    return ok, checks


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"bench: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cell = cells[args.workload]
    spec = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                      .read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    limits_file = BENCH / "limits" / f"{args.workload}.json"
    limits = (json.loads(limits_file.read_text())
              if limits_file.exists() else {})

    import jax

    from bench.lib.peaks import UnknownDevice, peaks

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"bench: JAX finds no TPU (platform {dev.platform!r})")
        return 1
    if len(devices) < cell["chips"]:
        log(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
            f"{len(devices)}")
        return 1
    try:
        peak = peaks(dev.device_kind)
    except UnknownDevice as e:
        log(f"bench: {e}")
        return 1
    log(f"compile_cache dir={setup_compile_cache()}")
    log(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__}")

    env = Env(dev, bool(args.trace), t0)
    out = run_cell(env, bench, args.workload, spec, mix, limits, peak,
                   args.seed, args.seconds)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": env.peak_bytes}
    if args.trace:
        device.update(out.pop("trace_device"))
    out["device"] = device
    out["checks"] = out.pop("checks")
    for name, c in out["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(out))
    return 0


def run_cell(env, bench, workload, spec, mix, limits, peak, seed, seconds):
    """Drive one run of ``workload`` and assemble the result line."""
    driver = importlib.import_module(f"bench.drivers.{mix['kind']}")
    res = driver.run(env, spec, mix, seed, seconds)
    correct, checks = judge(res["readings"], limits)
    metrics = {}
    if not env.tracing:
        e2e = dict(res["end_to_end"], setup_s=env.setup_s)
        for m in cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics}
    if env.tracing:
        from bench.lib import trace as T

        tr = env.trace
        ctx = {"spec": spec, "mix": mix, "layer": res["layer"],
               "trace": tr, "peaks": peak}
        for m in cell_metrics(bench, workload, "per_layer"):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev = T.first_device(tr)
        out["trace_device"] = {"busy_s": T.mean_busy_seconds(tr),
                               "window_s": T.window_seconds(tr)}
        out["breakdown"] = {"device_ops": T.top_ops(tr, dev),
                            "idle_gaps": T.idle_gaps(tr, dev)}
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
