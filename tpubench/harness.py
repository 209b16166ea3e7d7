"""One run of one cell: set-up, a measured window, the check, one line.

``run.py`` parses the command line and calls :func:`main`.  The traffic
driver of the cell's kind does the cell's own work through a
:class:`Run`, which owns what every cell shares: the device facts, the
compile cache, the compile counter, the benchmark's own spans, the
profiler window, the peak-memory reading and the result line.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from tpubench import registry

NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    (a fixed path: the path is part of the cache key)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(registry.CHECKOUT, ".jax_cache"))


def import_program():
    """Put ``<checkout>/src`` first on the path and check that the
    program comes from this checkout."""
    src = os.path.join(registry.CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"tpubench: no program at {src}")
    sys.path.insert(0, src)
    import repro
    where = os.path.dirname(os.path.abspath(repro.__file__))
    if where != os.path.join(src, "repro"):
        raise SystemExit(f"tpubench: repro imported from {where}, not from "
                         f"this checkout")
    return repro


def device_facts(chips: int) -> dict:
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX has "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def open_cell(cell: str) -> tuple[dict, dict]:
    """Import the program from this checkout, read ``BENCHMARK.json`` and
    check the chips the cell asks for.  Returns ``(bench, device
    facts)``; raises :class:`NoChip`."""
    import_program()
    bench = registry.benchmark()
    return bench, device_facts(registry.cell_entry(bench, cell)["chips"])


def use_compile_cache() -> None:
    """JAX's persistent cache at :func:`compile_cache_dir`, every entry
    kept, so a cell's second run in a checkout compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks_for(kind: str) -> dict:
    with open(os.path.join(registry.HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class Run:
    """What a traffic driver gets: the cell's data, the seed, and the
    shared instruments."""

    def __init__(self, cell: str, seed: int, seconds: float, trace: bool,
                 t_start: float, *, bench: dict | None = None,
                 config: dict | None = None, workload: dict | None = None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.bench = registry.benchmark() if bench is None else bench
        self.workload = (registry.workload(cell) if workload is None
                         else workload)
        self.config = (registry.config(self.workload["config"])
                       if config is None else config)
        self.mix = registry.traffic(self.workload["traffic"])
        self.spans: list[tuple[str, float, float]] = []
        self.compiles: list[tuple[float, str]] = []
        self.window_t: tuple[float, float] | None = None
        self.trace_dir: str | None = None
        self.memory_peak: int | None = None
        self.work_dir = tempfile.mkdtemp(prefix="tpubench-")

    # -- instruments ------------------------------------------------------------
    def listen_compiles(self) -> None:
        import jax

        def on_event(event: str, secs: float, fun_name: str = "?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.monotonic(), fun_name))

        jax.monitoring.register_event_duration_secs_listener(on_event)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: in the profiler's trace (``TraceAnnotation``) and
        in this run's own list, on the monotonic clock."""
        import jax
        t0 = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.spans.append((name, t0, time.monotonic()))

    def wrap(self, obj, attr: str, name: str) -> None:
        """Record every call of ``obj.attr`` as a span ``name``."""
        inner = getattr(obj, attr)

        def wrapped(*a, **kw):
            with self.span(name):
                return inner(*a, **kw)

        setattr(obj, attr, wrapped)

    @contextlib.contextmanager
    def window(self):
        """The measured window; with ``--trace 1`` the profiler records
        it.  Compiles inside it are counted."""
        import jax
        if self.trace:
            self.trace_dir = os.path.join(self.work_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t0 = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation("tpubench.window"):
                yield t0
        finally:
            t1 = time.monotonic()
            if self.trace:
                jax.profiler.stop_trace()
            self.window_t = (t0, t1)

    def compiles_in_window(self) -> list[str]:
        """Names of the programs compiled inside the last window."""
        t0, t1 = self.window_t
        return [name for t, name in self.compiles if t0 <= t <= t1]

    def read_memory_peak(self) -> None:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None

    def free(self) -> None:
        """Drop what the program left on the device before the reference
        runs."""
        gc.collect()

    def close(self) -> None:
        shutil.rmtree(self.work_dir, ignore_errors=True)


# -- the result ------------------------------------------------------------------


def _number(v):
    if v is None or (isinstance(v, float) and not math.isfinite(v)):
        return None
    return float(v)


def judge(checks: list[tuple[str, float, float]]) -> tuple[bool, dict]:
    """Each check is ``(name, value, limit)``: correct while ``value <=
    limit``; a missing or non-finite value fails."""
    out, ok = {}, True
    for name, value, limit in checks:
        v = _number(value)
        passed = v is not None and v <= limit
        ok &= passed
        out[name] = {"value": v, "limit": limit}
    return ok, out


def result_line(run: Run, out: dict, facts: dict) -> dict:
    """The contract's last line from a driver's ``out``."""
    from tpubench import metrics_io
    correct, checks = judge(out["checks"])
    device = dict(facts, memory_peak_bytes=run.memory_peak)
    if run.trace:
        out["device_kind"] = facts["kind"]
        reading = metrics_io.per_layer(run, out)
        metrics = reading["metrics"]
        device.update(busy_s=reading["busy_s"], window_s=reading["window_s"])
    else:
        metrics = {}
        for m in registry.cell_metrics(run.bench, run.cell, False):
            v = _number(out["e2e"].get(m["name"]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        reading = None
    line = {"correct": bool(correct and out["failed"] == 0),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": metrics, "device": device}
    if reading is not None and reading.get("breakdown"):
        line["breakdown"] = reading["breakdown"]
    line["checks"] = checks
    return line


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, facts: dict, *, bench: dict | None = None,
             config: dict | None = None,
             workload: dict | None = None) -> dict:
    """Set up, measure and check one cell on the device JAX gives us;
    returns the result line.  ``config``/``workload`` replace the files
    of those names (tests run tiny sizes on the CPU this way)."""
    use_compile_cache()
    run = Run(cell, seed, seconds, trace, t_start, bench=bench,
              config=config, workload=workload)
    try:
        run.listen_compiles()
        driver = registry.traffic_driver(run.mix["kind"])
        out = driver.run(run)
        return result_line(run, out, facts)
    finally:
        run.close()


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="tpubench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    try:
        bench, facts = open_cell(args.workload)
    except NoChip as e:
        print(f"tpubench: {e}", file=sys.stderr)
        return NO_CHIP
    line = run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start, facts, bench=bench)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
