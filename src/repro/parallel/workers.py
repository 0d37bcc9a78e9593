"""Worker-process side of the learner/worker executor (`repro.parallel`).

One `worker_main` loop runs in each spawn-started process of a
`ProcessExecutor` pool: pull a task from the shared queue, execute it, push
`(job_id, chunk_idx, status, payload)` back.  Tasks are whole stacked
k*L-run inner searches (`FanoutSearchSpec`, see `repro.core.bo`) -- exactly
the items a `SearchSession.pending()` emits, with their content-derived
seeds -- so the learner process keeps every outer GP/acquisition/session
state machine and workers only ever run embarrassingly-parallel inner work.

Module contract: **stdlib-only at import time**.  Workers must start with a
clean interpreter -- in particular they must not inherit the parent's jax
runtime or its x64 global state, which a fork-started child would copy
wholesale.  `ProcessExecutor` always uses the spawn start method, and this
module enforces the invariant at two points:

  * `worker_main` refuses to run searches in a fork-started child -- one
    where this module was imported by a *different* process (the PID
    sentinel below).  A spawn child re-imports everything fresh, so jax in
    `sys.modules` at boot merely means the parent's `__main__` module
    imports it (e.g. `examples/codesign_service.py`) -- that is clean,
    newly initialized state, not inheritance;
  * after any search whose resolved evaluation backend is "numpy", the
    worker verifies that no jax *evaluation-engine* module was pulled in
    (`repro.timeloop.batch_jax`, the Pallas kernels) and that the global
    `jax_enable_x64` flag is still off.  (The GP/BO surrogate layer itself
    is jax-based on every backend and scopes x64 per call -- see
    `repro.core.gp` -- so "never imports jax at all" is enforced only up to
    the moment a search runs; the regression test probes a fresh worker
    before its first search to pin that.)

The "probe" task kind returns a snapshot of the worker's module/x64 state
for that regression test (`tests/test_executor.py`).
"""

from __future__ import annotations

import os
import sys
import traceback

# jax modules that a numpy-backend search must never pull in: the batched
# device evaluation engine and the Pallas inner kernels.
_JAX_ENGINE_MODULES = ("repro.timeloop.batch_jax", "repro.kernels.edp_reduce")

# Fork-detection sentinel: a spawn-started worker re-imports this module in
# its own process (PID matches at `worker_main` time); a fork-started child
# inherits the parent's import (PID mismatch) -- and with it the parent's
# live jax runtime and x64 globals.
_IMPORT_PID = os.getpid()


def _jax_modules() -> list[str]:
    return sorted(m for m in sys.modules if m.split(".")[0] == "jax")


def _x64_enabled() -> bool:
    jax = sys.modules.get("jax")
    return bool(jax is not None and jax.config.jax_enable_x64)


def _jax_platform() -> str | None:
    """The backend jax runs on here: the initialized default once jax is
    loaded, else the platform `JAX_PLATFORMS` pins it to."""
    jax = sys.modules.get("jax")
    if jax is None:
        return os.environ.get("JAX_PLATFORMS")
    return jax.default_backend()


def _probe_report(inherited_jax: list[str]) -> dict:
    """Snapshot of the invariants the no-jax regression test pins."""
    return {
        "jax_platform": _jax_platform(),
        "inherited_jax": list(inherited_jax),
        "jax_modules": _jax_modules(),
        "engine_modules": [m for m in _JAX_ENGINE_MODULES if m in sys.modules],
        "x64_enabled": _x64_enabled(),
        "start_method": type(sys.modules.get("__mp_main__")).__name__
        if "__mp_main__" in sys.modules else None,
    }


def _run_search(spec, inherited_jax: list[str]) -> list:
    if inherited_jax:
        raise RuntimeError(
            f"fork-started worker inherited jax state from its parent "
            f"(modules {inherited_jax[:3]}...); ProcessExecutor workers must "
            "be spawn-started so the parent's jax runtime and x64 globals "
            "cannot leak in")
    entries = spec.run()
    if spec.engine is None or spec.engine.resolve_backend() == "numpy":
        loaded = [m for m in _JAX_ENGINE_MODULES if m in sys.modules]
        if loaded:
            raise RuntimeError(
                f"numpy-backend search imported jax evaluation modules in a "
                f"worker: {loaded}")
        if _x64_enabled():
            raise RuntimeError(
                "a worker search flipped the process-global jax_enable_x64 "
                "flag; x64 must stay scoped (jax.enable_x64(True))")
    return entries


def worker_main(task_q, result_q) -> None:
    """Persistent worker loop: runs until a `None` sentinel arrives.

    Tasks are `(kind, job_id, chunk_idx, payload)` tuples:
      ("search", jid, idx, FanoutSearchSpec) -> list of (mapping, EDP) entries
      ("probe",  jid, idx, None)             -> module/x64 state snapshot
    Results are `(job_id, chunk_idx, "ok", payload)` or
    `(job_id, chunk_idx, "error", (repr, traceback_text))` -- the learner
    re-raises errors with the worker traceback attached.
    """
    # jax modules count as *inherited* only under fork (module imported by a
    # different process); a spawn child whose __main__ imports jax booted
    # with fresh, unleaked state.
    forked = os.getpid() != _IMPORT_PID
    inherited_jax = _jax_modules() if forked else []
    while True:
        task = task_q.get()
        if task is None:
            return
        kind, jid, idx, payload = task
        try:
            if kind == "probe":
                out = _probe_report(inherited_jax)
            elif kind == "search":
                out = _run_search(payload, inherited_jax)
            else:
                raise ValueError(f"unknown worker task kind {kind!r}")
            result_q.put((jid, idx, "ok", out))
        except BaseException as e:  # noqa: BLE001 -- report, keep serving
            result_q.put((jid, idx, "error",
                          (repr(e), traceback.format_exc())))
