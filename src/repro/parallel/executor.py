"""Learner-side executors for stacked inner-search dispatch.

The actor/learner split of the co-design stack: the *learner* process owns
every outer GP, acquisition, and session state machine; *executors* decide
where the embarrassingly-parallel inner work -- whole stacked k*L-run
software searches, packaged as pickle-safe `FanoutSearchSpec`s -- actually
runs.  Content-derived probe seeds (`CodesignEngine.probe_seed`) make
evaluation order and placement free variables, so moving a spec between
processes provably cannot change results; worker-count invariance against
the goldens is pinned in `tests/test_executor.py`.

Two implementations share one small interface (`submit`/`ready`/`run`/
`close`, see `Executor`):

  `InlineExecutor`   runs every spec synchronously in the learner process.
                     Zero overhead, zero processes -- the historical
                     behavior, and the default.
  `ProcessExecutor`  a pool of persistent spawn-started worker processes
                     (`repro.parallel.workers.worker_main`) pulling specs
                     from a task queue.  Each submitted spec is split into
                     per-worker chunks (`ExecutorConfig.chunk_items`) and
                     reassembled in item order.  NumPy evaluation backend
                     first; the spec/queue interface is deliberately
                     placement-agnostic so a jax multi-device `shard_map`
                     executor can drop in behind the same four methods.

Spawn, never fork: a forked child would inherit the parent's jax runtime
and x64 globals (see `workers.py`, which asserts the invariant).  Workers are
pinned to the host CPU: an accelerator belongs to one process, the learner,
so the process executor only runs numpy-backend searches.
"""

from __future__ import annotations

import contextlib
import dataclasses
import multiprocessing as mp
import os
import queue as _queue
from typing import Any

from repro.core.config import EngineConfig, ExecutorConfig
from repro.parallel import workers as _workers


class Executor:
    """Interface: where a `FanoutSearchSpec` runs.

    submit(job_id, spec)   enqueue one spec; results surface via `ready`
    ready(block=False)     completed jobs as `[(job_id, entries), ...]`,
                           oldest first; block=True waits until at least one
                           job completes (no-op when nothing is in flight)
    run(spec)              synchronous convenience: submit + wait, returning
                           the entries directly (other in-flight jobs keep
                           their results queued for `ready`)
    close()                stop workers, if any; idempotent
    """

    kind = "base"

    def submit(self, job_id, spec) -> None:
        raise NotImplementedError

    def ready(self, block: bool = False) -> list[tuple[Any, list]]:
        raise NotImplementedError

    def run(self, spec) -> list:
        raise NotImplementedError

    def close(self) -> None:
        raise NotImplementedError


class InlineExecutor(Executor):
    """Run every spec synchronously in the calling (learner) process."""

    kind = "inline"

    def __init__(self) -> None:
        self._finished: list[tuple[Any, list]] = []

    def submit(self, job_id, spec) -> None:
        self._finished.append((job_id, spec.run()))

    def ready(self, block: bool = False) -> list[tuple[Any, list]]:
        out, self._finished = self._finished, []
        return out

    def run(self, spec) -> list:
        return spec.run()

    def close(self) -> None:
        pass


def _chunk_spec(spec, n_workers: int, chunk_items: int) -> list:
    """Split one spec into item-contiguous chunks (order-preserving).

    chunk_items <= 0 splits evenly across the pool.  An unsplit spec keeps
    its `pad_to` (the bucketed compile-cache hint only helps a whole stack);
    chunks drop it -- padding replays run 0 and is sliced off, so presence
    or absence never changes returned entries.
    """
    n = len(spec.items)
    if chunk_items <= 0:
        chunk_items = max(1, -(-n // max(1, n_workers)))
    if chunk_items >= n:
        return [spec]
    return [dataclasses.replace(spec, items=spec.items[i:i + chunk_items],
                                seeds=spec.seeds[i:i + chunk_items],
                                pad_to=None)
            for i in range(0, n, chunk_items)]


@contextlib.contextmanager
def _cpu_only_env():
    """Spawned children copy the parent's environment when they start, before
    they import anything: `JAX_PLATFORMS=cpu` there keeps every jax backend
    in them on the host CPU."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = old


class ProcessExecutor(Executor):
    """Persistent spawn-started worker pool behind two mp queues.

    Workers start lazily on first use and survive across jobs (one-time
    interpreter + import cost per worker, amortized over the pool's life).
    Chunk results are reassembled by (job_id, chunk_idx) in item order, so a
    job's entries come back exactly as an inline run would return them.
    Worker exceptions re-raise in the learner with the worker traceback.
    """

    kind = "process"

    def __init__(self, n_workers: int = 0, chunk_items: int = 0) -> None:
        self.n_workers = n_workers or ExecutorConfig().resolve_workers()
        self.chunk_items = chunk_items
        self._ctx = mp.get_context("spawn")
        self._procs: list = []
        self._tq = self._rq = None
        self._njobs = 0
        # job_id -> {"n": chunk count, "parts": {chunk_idx: payload}}
        self._pending: dict[Any, dict] = {}
        self._finished: list[tuple[Any, list]] = []

    # --- pool lifecycle ---------------------------------------------------------

    def _ensure_started(self) -> None:
        if self._procs:
            return
        self._tq = self._ctx.Queue()
        self._rq = self._ctx.Queue()
        with _cpu_only_env():
            for _ in range(self.n_workers):
                p = self._ctx.Process(target=_workers.worker_main,
                                      args=(self._tq, self._rq), daemon=True)
                p.start()
                self._procs.append(p)

    def close(self) -> None:
        if not self._procs:
            return
        for _ in self._procs:
            self._tq.put(None)
        for p in self._procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        self._procs = []
        for q in (self._tq, self._rq):
            q.close()
            q.cancel_join_thread()
        self._tq = self._rq = None
        self._pending.clear()

    def _check_alive(self) -> None:
        dead = [p for p in self._procs if not p.is_alive()]
        if dead and self._pending:
            codes = [p.exitcode for p in dead]
            raise RuntimeError(
                f"{len(dead)} executor worker(s) died (exit codes {codes}) "
                "with work in flight")

    # --- result plumbing --------------------------------------------------------

    def _accept(self, msg) -> None:
        jid, idx, status, payload = msg
        if status == "error":
            err, tb = payload
            raise RuntimeError(
                f"executor worker task failed: {err}\n--- worker traceback "
                f"---\n{tb}")
        job = self._pending[jid]
        job["parts"][idx] = payload
        if len(job["parts"]) == job["n"]:
            del self._pending[jid]
            if job.get("raw"):  # single-part non-list payload (probe)
                self._finished.append((jid, job["parts"][0]))
            else:
                self._finished.append(
                    (jid,
                     [e for i in range(job["n"]) for e in job["parts"][i]]))

    def _drain(self) -> None:
        while True:
            try:
                msg = self._rq.get(False)
            except _queue.Empty:
                return
            self._accept(msg)

    def _pump_until(self, pred) -> None:
        self._drain()
        while not pred():
            if not self._pending:
                raise RuntimeError(
                    "executor wait condition cannot be satisfied: no work "
                    "in flight")
            try:
                msg = self._rq.get(True, 1.0)
            except _queue.Empty:
                self._check_alive()
                continue
            self._accept(msg)

    # --- Executor interface -----------------------------------------------------

    def submit(self, job_id, spec) -> None:
        if job_id in self._pending:
            raise ValueError(f"job id {job_id!r} already in flight")
        _check_backend(spec.engine)
        self._ensure_started()
        chunks = _chunk_spec(spec, self.n_workers, self.chunk_items)
        self._pending[job_id] = {"n": len(chunks), "parts": {}}
        for idx, chunk in enumerate(chunks):
            self._tq.put(("search", job_id, idx, chunk))

    def ready(self, block: bool = False) -> list[tuple[Any, list]]:
        if block and not self._finished and self._pending:
            self._pump_until(lambda: bool(self._finished))
        else:
            self._drain()
        out, self._finished = self._finished, []
        return out

    def _wait(self, jid) -> Any:
        while True:
            for i, (j, payload) in enumerate(self._finished):
                if j == jid:
                    del self._finished[i]
                    return payload
            self._pump_until(
                lambda: any(j == jid for j, _ in self._finished))

    def run(self, spec) -> list:
        jid = ("_run", self._njobs)
        self._njobs += 1
        self.submit(jid, spec)
        return self._wait(jid)

    def probe(self) -> dict:
        """State snapshot from one worker (the no-jax regression surface)."""
        self._ensure_started()
        jid = ("_probe", self._njobs)
        self._njobs += 1
        self._pending[jid] = {"n": 1, "parts": {}, "raw": True}
        self._tq.put(("probe", jid, 0, None))
        return self._wait(jid)


def _check_backend(engine) -> None:
    """A CPU-pinned worker running a jax-backend search would be a hidden
    CPU fallback for the accelerator path: refuse it."""
    if (engine or EngineConfig()).resolve_backend() == "jax":
        raise ValueError(
            "the process executor runs numpy-backend searches only: its "
            "workers are pinned to the host CPU, and the accelerator belongs "
            "to the learner process (use ExecutorConfig(kind='inline') with "
            "backend='jax')")


def make_executor(cfg: ExecutorConfig | None = None) -> Executor:
    """Build the executor an `ExecutorConfig` describes."""
    cfg = cfg if cfg is not None else ExecutorConfig()
    if cfg.kind == "inline":
        return InlineExecutor()
    return ProcessExecutor(n_workers=cfg.resolve_workers(),
                           chunk_items=cfg.chunk_items)
