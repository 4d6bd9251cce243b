"""Tests of the on-chip benchmark that run on the CPU in seconds: the trace
reduction on a recorded trace, the work counts and the peaks table, the
per-layer readers, and each cell driven end to end at a tiny size with the
look for a chip left out, as the program is (its result line) and with the
timed path broken underneath (``correct`` must come out false)."""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import faults  # noqa: E402
import named  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from metrics import work  # noqa: E402

RECORDED_TRACE = os.path.join(BENCH, "testdata", "taxi_small.xplane.pb")

#: each cell at a tiny size; the kernels run in Pallas interpret mode
TINY = {
    # tol tight enough that the answer's residual is mostly cancellation,
    # where a lower precision shows
    "taxi.solve": {"n": 512, "block_size": 64, "rank": 16, "tol": 0.05,
                   "max_iters": 300, "eval_every": 10, "warmup_iters": 10},
}
FAULTS = {"taxi.solve": ("step_unchanged", "half_batch", "answer_altered")}
MIN_COMPILE = "jax_persistent_cache_min_compile_time_secs"


@pytest.fixture(autouse=True)
def no_chip_needed(monkeypatch):
    """The harness's look for a chip is the one part a CPU run leaves out."""
    monkeypatch.setattr(run, "require_chips", lambda chips, backend: None)


def tiny_run(cell: str, swap: str | None = None, seed: int = 2**33 + 3) -> dict:
    spec = run.load_cell(cell)
    spec["config"].update(TINY[cell], backend="interpret")
    ctx = (contextlib.nullcontext() if swap is None
           else faults.SWAPS[swap](spec["config"]))
    with ctx:
        return run.run_cell(spec, seed, 0.2, False)


def test_interval_arithmetic():
    E = trace_reduce.Event
    events = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40)]
    assert trace_reduce.merge([(e.start, e.end) for e in events]) == [(0, 20), (30, 40)]
    assert trace_reduce.busy_ns(events, 0, 50) == 30
    assert trace_reduce.busy_ns(events, 8, 35) == 17
    assert trace_reduce.idle_gaps(events, 0, 50) == [(20, 30), (40, 50)]
    assert trace_reduce.op_seconds(events, 0, 50) == {"a": 1e-8, "b": 1.5e-8, "c": 1e-8}
    grouped = trace_reduce.ops_by_module(events, [E("jit_f(1)", 0, 25), E("jit_g(2)", 28, 45)])
    assert {k: [e.name for e in v] for k, v in grouped.items()} == {
        "jit_f(1)": ["a", "b"], "jit_g(2)": ["c"]}
    gaps = [(20, 30), (40, 50)]
    named = trace_reduce.name_gaps(gaps, [("compile", 18, 28)], default="idle")
    assert named == [["idle", 1.2e-8], ["compile", 8e-9]]
    # a name listed first takes precedence over one that also covers a gap
    named = trace_reduce.name_gaps(gaps, [("compile", 22, 25), ("solve", 0, 45)])
    assert named == [["solve", 1.2e-8], ["host, no span", 5e-9], ["compile", 3e-9]]


def test_trace_reduce_on_recorded_trace():
    t = trace_reduce.load(RECORDED_TRACE)
    win = trace_reduce.host_span(t, "bench.window")
    s = trace_reduce.summarize(t, win.start, win.end)
    assert 0 < s.busy_s < s.window_s
    assert work.kernel_seconds(s) > 0
    assert "jit_kernel_matvec_pallas" in s.program_s and "jit_step" in s.program_s
    # the programs' busy time adds up to the device's, and no kernel event
    # lies outside the programs
    assert sum(s.program_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    in_programs = sum(v for ops in s.program_op_s.values() for v in ops.values())
    assert in_programs == pytest.approx(sum(s.op_s.values()), rel=1e-6)
    idle = sum(e - b for b, e in s.gaps) / 1e9
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


@pytest.mark.parametrize("d,t,per_entry", [(9, 1, 25), (784, 10, 1593)])
def test_matvec_work_counts_the_problem_not_the_tile(d, t, per_entry):
    m, n = 100, 1000
    assert work.matvec_flops(m, n, d, t) == m * n * per_entry
    assert work.matvec_bytes(m, n, d, t) == 4 * (m * d + n * d + n * t + m * t)


def test_peaks_table():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert work.least_seconds(197e12, 0, "TPU v5 lite") == 1.0
    assert work.least_seconds(0, 819e9, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v99")


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "taxi.solve",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "runs on the chip only" in proc.stderr


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_prints_the_contract_line(cell):
    import jax

    min_compile = getattr(jax.config, MIN_COMPILE)
    res = tiny_run(cell)
    # a run leaves the process's settings as it found them
    assert getattr(jax.config, MIN_COMPILE) == min_compile
    spec = run.load_cell(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("cell,swap", [(c, "control") for c in sorted(TINY)]
                         + [(c, f) for c in sorted(FAULTS) for f in FAULTS[c]])
def test_broken_timed_path_is_not_correct(cell, swap):
    res = tiny_run(cell, swap)
    assert res["correct"] is False, res["checks"]


def test_compile_clock_listens_only_while_entered():
    from jax import monitoring

    event = run.CompileClock.EVENTS[-1]
    with run.CompileClock() as clock:
        monitoring.record_event_duration_secs(event, 2.0)
    monitoring.record_event_duration_secs(event, 3.0)
    assert clock.seconds == {event: 2.0}


def _solve_record(summary):
    """What a window of two solves of the taxi cell records."""
    return {"summary": summary, "device_kind": "TPU v5 lite", "window_s": 10.0,
            "compile_s": {run.CompileClock.EVENTS[-1]: 2.0},
            "iters": [50, 50], "resid_at_stop": [0.158, 0.16], "checks": [2, 2],
            "n": 2**18, "d": 9, "t": 1, "b": 131, "kernel": "rbf"}


PER_LAYER = run._json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]


@pytest.mark.parametrize("metric", PER_LAYER, ids=[m["name"] for m in PER_LAYER])
def test_per_layer_reader(metric):
    check, step = "%kernel_matvec_pallas.1", "%kernel_matvec_pallas.2"
    summary = trace_reduce.Summary(
        window_s=10.0, busy_s=6.0, op_s={check: 5.0, step: 0.1, "%fusion": 0.9},
        program_s={"jit_kernel_matvec_pallas": 5.0, "jit_step": 1.0},
        program_op_s={"jit_kernel_matvec_pallas": {check: 5.0},
                      "jit_step": {step: 0.1, "%fusion": 0.9}},
        gaps=[], lo=0, hi=10**10)
    reader = named.load("metrics", metric["name"])
    value = reader.read(_solve_record(summary))
    assert value is not None and value > 0
    if metric["unit"] == "%":
        assert value <= 100
    # a reader that finds nothing to read returns nothing
    empty = {k: type(v)() if isinstance(v, (list, dict)) else v
             for k, v in _solve_record(None).items()}
    empty["window_s"] = 0.0
    assert reader.read(empty) is None
