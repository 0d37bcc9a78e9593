"""Counters and spans of the co-design search, on the profiler's clock.

Counters are always on.  `COUNTERS` is one process-wide tally that the code
bumps where the work happens; `counters_snapshot()` copies it, and the
difference of two snapshots is the reading over the stretch between them.

  hw_feat_*, sw_feat_*, sw_fwd_*  `SlotCache` hits and misses
                                   (`repro.core.cache`)
  forward.rows         mapping rows handed to the fused forward
                       (`batch_jax.forward_device`, `forward_device_stacked`);
                       the all-ones rows of runs that sit a round out
                       (`batch.PaddingPool`) are not counted
  forward.slots        rows the forward program ran: the pool length rounded
                       up to its bucket, times the runs of a stack
  gp.fits              stacked GP fits (`GPStack.fit`, which
                       `GPClassifierStack.fit` calls once a fit)
  gp.runs              runs those fits stacked: gp.runs / gp.fits is the
                       mean stack width
  gp.rows              observations of the stacked GP fits (`GPStack.fit`)
  gp.slots             rows those fits ran: runs x `gp._bucket_stack`
  gp.host_fits         stacked fits placed on the host's CPU device
                       (`gp._fit_device`: where the default backend is a
                       TPU); their copies onto the CPU device are host
                       memory and not in transfer.h2d_bytes
  gp.lowrank_fits      stacked fits through the Woodbury NLL
                       (`gp._fit_lowrank`: the linear kernel above
                       `gp._LOWRANK_MIN_ROWS` padded rows)
  transfer.h2d_bytes   bytes copied to the device for the search's programs
                       (`to_device`)
  transfer.d2h_bytes   bytes of device results fetched to the host (`fetch`)
  device.dispatches    jitted programs launched on the search path: the
                       forward, the EDP lower bounds, the stacked GP's fit
                       (on the host's CPU too, see gp.host_fits), scoring
                       and posterior, the outer GP's fit, posterior and
                       rank-1 update
  trace.dropped        span records dropped because the record list was full

Spans are off until `enable()` and off again after `disable()`; those two
calls are the only switch.  Off, `span(name)` costs one module-level check
and returns a shared no-op context.  On, each span records a `Span`
(`time.perf_counter_ns` start and end, the enclosing span's id, the id of the
search being stepped) and enters `jax.profiler.TraceAnnotation(name)`, so a
profiler trace taken meanwhile shows it beside the device's programs.  The
names, one per layer of a search:

  codesign.outer     one outer step (`SearchSession.step`): outer BO
                     bookkeeping, the hardware pool, the bound gate
  codesign.outer_gp  the outer GP and classifier: refits and pool scoring
                     (`BOLoop`)
  codesign.inner     one lockstep inner search (`bo_maximize_many`)
  codesign.sample    host sampling of the inner searches' candidate pools
  codesign.forward   the fused cost-model forward's call sites (`swspace`)
  codesign.gp        the stacked GP's fit and scoring (`GPStack`,
                     `GPClassifierStack`), and the GP of a single inner
                     search (`BOLoop` of `bo_maximize`)
  codesign.wait      the host blocked on a device result (`fetch`)

`spans()` returns the records of spans that ended since `enable()`.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# The process-wide tallies (see the module docstring for the names).
COUNTERS: collections.Counter = collections.Counter()

# Records kept at most; later ones are counted in COUNTERS["trace.dropped"].
MAX_SPANS = 1 << 18


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    parent: int | None      # id of the enclosing span, None at top level
    search: int | None      # `next_search_id()` of the stepping session


_on = False
_records: list[Span] = []
_ids = itertools.count()
_search_ids = itertools.count()
_local = threading.local()
_NOOP = contextlib.nullcontext()


def counters_snapshot() -> dict[str, int]:
    """Copy of the counters (diff two snapshots for a reading)."""
    return dict(COUNTERS)


def enable() -> None:
    """Record spans from now on; drops the records of an earlier enable."""
    global _on
    _records.clear()
    _on = True


def disable() -> None:
    """Stop recording spans; `spans()` still returns what was recorded."""
    global _on
    _on = False


def spans() -> list[Span]:
    """The spans that ended since `enable()`, in the order they ended."""
    return list(_records)


def next_search_id() -> int:
    """A new id for the spans of one search (`SearchSession`)."""
    return next(_search_ids)


class _Open:
    __slots__ = ("name", "search", "id", "parent", "start", "annotation")

    def __init__(self, name: str, search: int | None):
        self.name, self.search = name, search

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        top = stack[-1] if stack else None
        self.parent = top.id if top is not None else None
        if self.search is None and top is not None:
            self.search = top.search
        self.id = next(_ids)
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        stack.append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        _local.stack.pop()
        self.annotation.__exit__(*exc)
        if len(_records) < MAX_SPANS:
            _records.append(Span(self.id, self.name, self.start, end,
                                 self.parent, self.search))
        else:
            COUNTERS["trace.dropped"] += 1
        return False


def span(name: str, search: int | None = None):
    """Context manager timing one layer's work.  `search` tags the span and
    those inside it (they inherit it when None)."""
    if not _on:
        return _NOOP
    return _Open(name, search)


def to_device(x, dtype=None) -> jax.Array:
    """`jnp.asarray(x, dtype)`, counting the bytes a host array copies to the
    device (an array already there copies none)."""
    out = jnp.asarray(x, dtype)
    if not isinstance(x, jax.Array):
        COUNTERS["transfer.h2d_bytes"] += out.nbytes
    return out


def fetch(x, dtype=None) -> np.ndarray:
    """`np.asarray(x, dtype)` of a device result, inside a `codesign.wait`
    span, counting the bytes that come back."""
    with span("codesign.wait"):
        out = np.asarray(x, dtype=dtype)
    if isinstance(x, jax.Array):
        COUNTERS["transfer.d2h_bytes"] += x.nbytes
    return out


def dispatched() -> None:
    """Count one jitted program launched."""
    COUNTERS["device.dispatches"] += 1
