"""Reduction of the program's span records to the self time of each layer of
a search over a window.

The records are those of `repro.core.trace.spans()`: each has an `id`, a
`name`, `start_ns` and `end_ns` on `time.perf_counter_ns`'s clock, and the
`parent` id of the span it ran inside (None at top level).  The layers are
the spans named in `LAYERS`.  A layer span's self time is its interval,
clipped to the window, less the part its child layer spans cover.  A
`codesign.wait` span (the host blocked on a device result) is not a layer:
it counts in its parent's self time and, separately, in the wait total.
The window less the union of the top-level layer spans is the unattributed
time.  So the layers' self times and the unattributed time add up to the
window, where the spans come from one thread.
"""

from __future__ import annotations

import collections
import dataclasses

LAYERS = ("codesign.outer", "codesign.outer_gp", "codesign.inner",
          "codesign.sample", "codesign.forward", "codesign.gp")
WAIT = "codesign.wait"


@dataclasses.dataclass
class LayerTimes:
    window_s: float
    self_s: dict[str, float]    # self seconds by layer span name
    wait_s: float               # seconds inside codesign.wait spans
    unattributed_s: float       # the window outside every top-level layer

    def share(self, seconds: float) -> float:
        """`seconds` as a percentage of the window."""
        return 100.0 * seconds / self.window_s


def _covered(intervals, lo: int, hi: int) -> int:
    """Length of the union of `intervals` clipped to [lo, hi)."""
    total, end = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def reduce(records, lo_ns: int, hi_ns: int) -> LayerTimes:
    """Self seconds of each layer over the window [lo_ns, hi_ns)."""
    if hi_ns <= lo_ns:
        raise ValueError(f"empty window [{lo_ns}, {hi_ns})")
    layer = [r for r in records if r.name in LAYERS]
    ids = {r.id for r in layer}
    children = collections.defaultdict(list)
    for r in layer:
        children[r.parent if r.parent in ids else None].append(
            (r.start_ns, r.end_ns))
    self_ns = collections.Counter({name: 0 for name in LAYERS})
    for r in layer:
        lo, hi = max(r.start_ns, lo_ns), min(r.end_ns, hi_ns)
        if hi > lo:
            self_ns[r.name] += (hi - lo) - _covered(children[r.id], lo, hi)
    wait_ns = sum(max(0, min(r.end_ns, hi_ns) - max(r.start_ns, lo_ns))
                  for r in records if r.name == WAIT)
    window = hi_ns - lo_ns
    return LayerTimes(
        window_s=window * 1e-9,
        self_s={k: v * 1e-9 for k, v in self_ns.items()},
        wait_s=wait_ns * 1e-9,
        unattributed_s=(window - _covered(children[None], lo_ns, hi_ns))
        * 1e-9)
