"""Reduction of one JAX profiler trace (`.xplane.pb`) to the numbers the
benchmark reports.

    python3 bench/xplane.py <file.xplane.pb>    # print the trace's layout

Device time comes from the `XLA Modules` line of each device plane
(`/device:TPU:<n>`): one event per run of a compiled program
(`jit_<function>(<hash>)`).  A program keeps the device busy from its start
to its end (its loops run on the device), so busy time is the union of
program intervals.  The `XLA Ops` line, one event per operation run, is not
read: the emulated float64 linear algebra of the GP makes millions of them
a second.  They also fill the profiler's buffer within the first second or
so of device time (on a TPU v5e), after which nothing more is recorded.  So
the traced window is the part of the window span that the trace covers:
from its start to the end of the last program run recorded in it.  Host spans are the `TraceAnnotation`s the harness
writes (names starting with `SPAN_PREFIX`), on the same clock.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULES_LINE = "XLA Modules"
TOP_N = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # the traced window (see module doc)
    span_s: float                   # the whole window span
    busy_s: float                   # union of program intervals, mean over
    n_devices: int                  # the devices
    module_s: dict[str, float]      # device seconds per program run name
    module_runs: dict[str, int]     # runs per program run name
    idle_by_span: dict[str, float]  # idle seconds by innermost host span


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _innermost(spans, t):
    """Name of the shortest host span covering time t."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside spans"


def summarize(profile, window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Reduce a `jax.profiler.ProfileData` to a `TraceSummary`."""
    spans, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == window_span]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {window_span!r} spans, "
                         "not one")
    lo, hi = windows[0]
    spans = [sp for sp in spans if sp[2] != window_span]
    if not devices:
        raise ValueError("trace holds no device plane")

    runs_by_device = []
    for plane in devices:
        runs = []
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if e > s:
                    runs.append((s, e, ev.name))
        runs_by_device.append(runs)
    ends = [e for runs in runs_by_device for _, e, _ in runs]
    if not ends:
        raise ValueError("trace holds no program run inside the window")
    covered = max(ends)

    busy_total = 0.0
    module_s = collections.Counter()
    module_runs = collections.Counter()
    idle = collections.Counter()
    for runs in runs_by_device:
        for s, e, name in runs:
            module_s[name] += (e - s) * 1e-9
            module_runs[name] += 1
        merged = _union([(s, e) for s, e, _ in runs])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [covered]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle[_innermost(spans, (s + e) // 2)] += (e - s) * 1e-9
    n = len(devices)
    return TraceSummary(
        window_s=(covered - lo) * 1e-9,
        span_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n,
        n_devices=n,
        module_s={k: v / n for k, v in module_s.items()},
        module_runs=dict(module_runs),
        idle_by_span={k: v / n for k, v in idle.items()},
    )


def program_name(run_name: str) -> str:
    """`jit__fit_stack(9421936244859085542)` -> `jit__fit_stack`."""
    return run_name.split("(", 1)[0]


def breakdown(summary: TraceSummary) -> dict:
    """The `breakdown` of a traced result line: the programs that took most
    device time, and idle time by what the host was doing."""
    programs = collections.Counter()
    for name, seconds in summary.module_s.items():
        programs[program_name(name)] += seconds

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]
    return {"device_ops": top(programs),
            "idle_gaps": top(summary.idle_by_span)}


def start(log_dir: str) -> None:
    """Start the profiler as every traced run does."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _describe(path: str) -> None:
    for plane in load(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:3]:
                print(f"    {ev.name!r} start {ev.start_ns} "
                      f"dur {ev.duration_ns}")


if __name__ == "__main__":
    _describe(sys.argv[1])
