"""From a JAX profiler trace to device busy and idle time, per-kernel time
and per-program time.

``jax.profiler.trace`` writes an ``.xplane.pb`` file; ``ProfileData`` reads
it with nothing but JAX.  A TPU plane (``/device:TPU:<i>``) has one line of
operation events (``XLA Ops``) and one of program events (``XLA Modules``);
the host plane's ``python`` line holds the spans the benchmark writes with
``jax.profiler.TraceAnnotation``.  Times are nanoseconds on the trace's own
clock.

The reduction is plain interval arithmetic over those events, kept apart from
the reading so that it can be checked on a small recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]  # device plane -> operation events
    modules: dict[str, list[Event]]  # device plane -> program events
    host: list[Event]  # the benchmark's own spans


def find_file(directory: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory!r}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read the device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                into = ops if line.name == OPS_LINE else modules
                into[plane.name] = [
                    Event(op_name(e.name), int(e.start_ns), int(e.end_ns))
                    for e in line.events
                ]
            elif plane.name == "/host:CPU":
                host.extend(
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events if e.name.startswith("bench.")
                )
    return Trace(ops=ops, modules=modules, host=host)


def op_name(hlo_text: str) -> str:
    """``%fusion.7 = f32[...] fusion(...)`` -> ``%fusion.7``."""
    return hlo_text.split(" = ", 1)[0]


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def busy_ns(events, lo: int, hi: int) -> int:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(events, lo, hi)))


def idle_gaps(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that no event covers."""
    gaps, t = [], lo
    for s, e in merge(clip(events, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_seconds(events, lo: int, hi: int) -> dict[str, float]:
    """Device seconds per operation name inside [lo, hi]."""
    out: dict[str, float] = {}
    for e in events:
        if e.end > lo and e.start < hi:
            out[e.name] = out.get(e.name, 0.0) + (min(e.end, hi) - max(e.start, lo)) / 1e9
    return out


def ops_by_module(ops, modules) -> dict[str, list[Event]]:
    """Operation events grouped by the program event that holds them."""
    mods = sorted(modules, key=lambda m: m.start)
    out: dict[str, list[Event]] = {}
    i = 0
    for op in sorted(ops, key=lambda e: e.start):
        while i < len(mods) and mods[i].end <= op.start:
            i += 1
        if i < len(mods) and mods[i].start <= op.start:
            out.setdefault(mods[i].name, []).append(op)
    return out


def program_name(module_event_name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``: the program's name without the
    per-compilation id the trace appends."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


@dataclasses.dataclass
class Summary:
    """What the per-layer metrics read from one traced window."""

    window_s: float
    busy_s: float  # mean over the chips' planes
    op_s: dict[str, float]  # device seconds per operation name
    program_s: dict[str, float]  # device-busy seconds per program name
    program_op_s: dict[str, dict[str, float]]  # program -> op -> seconds
    gaps: list[tuple[int, int]]  # idle stretches of the first chip (ns)
    lo: int
    hi: int


def summarize(trace: Trace, lo: int, hi: int) -> Summary:
    """Reduce the window [lo, hi] (ns on the trace clock) of ``trace``."""
    planes = sorted(trace.ops)
    if not planes:
        raise ValueError("the trace holds no TPU operation events")
    busy = [busy_ns(trace.ops[p], lo, hi) for p in planes]
    op_s: dict[str, float] = {}
    program_s: dict[str, float] = {}
    program_op_s: dict[str, dict[str, float]] = {}
    for p in planes:
        for name, s in op_seconds(trace.ops[p], lo, hi).items():
            op_s[name] = op_s.get(name, 0.0) + s
        grouped = ops_by_module(trace.ops[p], trace.modules.get(p, []))
        for mod, events in grouped.items():
            prog = program_name(mod)
            program_s[prog] = program_s.get(prog, 0.0) + busy_ns(events, lo, hi) / 1e9
            per = program_op_s.setdefault(prog, {})
            for name, s in op_seconds(events, lo, hi).items():
                per[name] = per.get(name, 0.0) + s
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        op_s=op_s,
        program_s=program_s,
        program_op_s=program_op_s,
        gaps=idle_gaps(trace.ops[planes[0]], lo, hi),
        lo=lo,
        hi=hi,
    )


def host_span(trace: Trace, name: str) -> Event:
    """The benchmark's span ``name`` (the window it traced)."""
    for e in trace.host:
        if e.name == name:
            return e
    raise KeyError(f"the trace has no host span {name!r}")


def subtract(intervals, cut) -> list[tuple[int, int]]:
    """The parts of sorted disjoint ``intervals`` that sorted disjoint
    ``cut`` does not cover."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(cut) and cut[k][0] < e:
            if cut[k][0] > t:
                out.append((t, cut[k][0]))
            t = max(t, cut[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def name_gaps(gaps, spans, top: int = 10, default: str = "host, no span") -> list[list]:
    """Idle seconds per host activity.  ``spans`` are ``(name, start_ns,
    end_ns)``; a name listed earlier takes precedence, so an idle stretch
    under a compile inside a solve counts as compile.  What no span covers
    counts as ``default``.  The ``top`` largest totals, largest first."""
    order: list[str] = []
    unions: dict[str, list[tuple[int, int]]] = {}
    for name, s, e in spans:
        if name not in unions:
            order.append(name)
            unions[name] = []
        unions[name].append((s, e))
    left = merge(gaps)
    totals: dict[str, float] = {}
    for name in order:
        rest = subtract(left, merge(unions[name]))
        taken = sum(e - s for s, e in left) - sum(e - s for s, e in rest)
        if taken:
            totals[name] = taken / 1e9
        left = rest
    if left:
        totals[default] = sum(e - s for s, e in left) / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]
