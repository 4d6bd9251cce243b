"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload taxi.solve --seed 7 --seconds 51 --trace 0

A cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<traffic>.json``, whose ``load`` key picks a load in
``load.py``) and the limits of its correctness check
(``bench/limits/<cell>.json``).  Each per-layer metric is read by
``bench/metrics/<metric>.py``.  A run makes its inputs from ``--seed``, warms
up every shape the window uses (set-up), measures for ``--seconds``, then
compares what the window produced with the plain reference (``reference.py``)
and prints one JSON line last on standard output.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` records a profiler trace of the
window and reports its per-layer metrics instead.

It runs on TPU chips only: where JAX finds no TPU, fewer chips than the cell
asks for, or a kernel backend other than Pallas, it exits non-zero and prints
no result.  JAX's persistent compilation cache is kept in ``.jax_cache/`` at
the root of the checkout, where ``repro.launch.runtime.use_compile_cache``
places it (``JAX_COMPILATION_CACHE_DIR``, set before JAX starts).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for; the run prints no result."""


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """Everything a run of cell ``name`` needs, found by name."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def for_cell(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "name": name,
        "chips": cell["chips"],
        "config": _json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")),
        "limits": _json(os.path.join(BENCH, "limits", name + ".json")),
        "end_to_end": for_cell(bench["end_to_end"]),
        "per_layer": for_cell(bench["per_layer"]),
    }


def require_chips(chips: int, backend: str) -> None:
    """A TPU with at least ``chips`` chips, serving Pallas kernels."""
    import jax

    from repro.kernels.ops import resolve_backend

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    if resolve_backend(backend) != "pallas":
        raise NoChip(f"kernel backend {resolve_backend(backend)!r}, not 'pallas'")


class CompileClock:
    """Compilation seen through ``jax.monitoring`` while the clock is
    entered: seconds per phase (tracing, lowering, and the backend compile,
    which holds any load from the persistent cache), each phase's time span,
    and the persistent cache's hits and misses."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )
    CACHE = ("/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses")

    def __init__(self):
        self.reset()

    def __enter__(self) -> "CompileClock":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_time_span_listener(self._span)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_time_span_listener(self._span)
        monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds[event] = self.seconds.get(event, 0.0) + duration

    def _span(self, event: str, start: float, end: float, **_) -> None:
        if event in self.EVENTS:
            self.spans.append((event.rsplit("/", 1)[-1], start, end))

    def _event(self, event: str, **_) -> None:
        if event in self.CACHE:
            key = event.rsplit("/", 1)[-1]
            self.cache[key] = self.cache.get(key, 0) + 1

    def reset(self) -> None:
        self.seconds: dict[str, float] = {}
        self.spans: list[tuple[str, float, float]] = []  # wall-clock seconds
        self.cache: dict[str, int] = {}


def _trace_dir(name: str) -> str:
    path = os.path.join(OUT_DIR, "trace", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _reduce_trace(trace_path: str, rec: dict, device: dict, clock: CompileClock,
                  wall0: int) -> dict:
    """Read the window's trace into ``rec["summary"]`` and ``device``; return
    the breakdown: the device operations that took most time, and idle time
    by what the host was doing (compiling, inside ``solve()``)."""
    import trace_reduce

    t = trace_reduce.load(trace_reduce.find_file(trace_path))
    win = trace_reduce.host_span(t, "bench.window")
    summary = trace_reduce.summarize(t, win.start, win.end)
    rec["summary"] = summary
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    # the monitoring spans are on the wall clock, which starts with the
    # window's span
    to_trace = win.start - wall0
    spans = [("compile: " + name, int(s * 1e9) + to_trace, int(e * 1e9) + to_trace)
             for name, s, e in clock.spans]
    spans += [("solve() host work", e.start, e.end) for e in t.host
              if e.name.startswith("bench.solve.")]
    return {
        "device_ops": sorted(
            ([f"{prog}/{op}", sec] for prog, ops in summary.program_op_s.items()
             for op, sec in ops.items()), key=lambda kv: -kv[1])[:10],
        "idle_gaps": trace_reduce.name_gaps(summary.gaps, spans, default="outside solve()"),
    }


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START) -> dict:
    """One run of a cell; returns the result line as a dict."""
    import jax

    from repro.kernels.ops import resolve_backend

    import load
    import named

    config, traffic = spec["config"], spec["traffic"]
    require_chips(spec["chips"], config["backend"])
    mix = load.LOADS[traffic["load"]](config, traffic, spec["limits"], seed)
    with CompileClock() as clock:
        mix.setup()
        clock.reset()
        trace_path = _trace_dir(spec["name"]) if trace else None
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the benchmark's own spans suffice
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_path, profiler_options=options)
        t_window = time.monotonic()
        wall0 = time.time_ns()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                mix.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
    setup_s = t_window - t_start
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "device_kind": dev.device_kind,
        "count": jax.device_count(),
        "backend": resolve_backend(config["backend"]),
        "memory_peak_bytes": int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)),
    }
    mix.release()

    rec = {
        "device_kind": dev.device_kind,
        "window_s": mix.window_s,
        "compile_s": dict(clock.seconds),
        "cache_events": dict(clock.cache),
        "summary": None,
        **mix.record(),
    }
    breakdown = None
    if trace:
        breakdown = _reduce_trace(trace_path, rec, device, clock, wall0)

    checks = mix.check()
    correct = all(c.value <= c.limit for c in checks)

    values: dict[str, float | None] = {}
    if trace:
        for m in spec["per_layer"]:
            values[m["name"]] = named.load("metrics", m["name"]).read(rec)
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    else:
        e2e = {"setup_s": setup_s, **mix.end_to_end()}
        for m in spec["end_to_end"]:
            values[m["name"]] = e2e[m["name"]]
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    result = {
        "correct": correct,
        "attempted": mix.attempted,
        "failed": mix.failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in values.items()
                    if v is not None},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["diagnostics"] = mix.diagnostics() + [
        f"window: persistent-cache events {rec['cache_events']}"]
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def start_process() -> None:
    """Settings of the whole process, made before JAX starts: the
    persistent compilation cache in the checkout, whatever the environment
    names, keeping every program of the cell, so that a run after the first
    compiles nothing it has seen; and the program on the import path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    start_process()
    spec = load_cell(args.workload)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}; this benchmark runs on the chip only", file=sys.stderr)
        return 2
    for line in result.pop("diagnostics"):
        print(line, file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
