"""One run of one cell of the benchmark in BENCHMARK.json, on the chip.

    python3 bench/run.py --workload resnet18.search --seed 7 --seconds 40 \
        --trace 0

Everything a cell names is found by name: its configuration in
`bench/configs/<config>.json` (with its plain references of the cost
model and of the surrogate GP, `bench/references/<name>.py`), its traffic in
`bench/traffic/<traffic>.json`, and each per-layer metric in
`bench/metrics/<metric>.py`.

Set-up (timed as `setup_s` from process start): find the chips (a run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result), turn on the compile cache at its fixed path inside the
checkout (`repro.jax_cache`), and run the traffic's round of searches once,
which compiles or loads every program the window uses.  The window runs the
same searches, `CodesignEngine(config).session(layers)` stepped to
completion, in whole rounds (`traffic.closed_loop`).  `search_s` is the
window's time over the searches it finished.  With `--trace 1` the window
runs under the profiler, each layer's calls are timed, and the cell's
per-layer metrics are reported instead.  After the window every forward
call, every stacked GP scoring and every finished search is checked against
the plain references (`checks.py`).

The last line of standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's entry and every file it names, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = traffic.load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    reference, surrogate_reference = (
        load_module(os.path.join(HERE, "references", name + ".py"),
                    "reference_" + name)
        for name in (config["reference"], config["surrogate"]["reference"]))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    per_layer = [(m, load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_")))
                 for m in bench["per_layer"] if applies(m)]
    return {"cell": cell, "config": config, "traffic": mix,
            "reference": reference,
            "surrogate_reference": surrogate_reference,
            "end_to_end": end_to_end,
            "per_layer": per_layer}


def find_devices(chips: int) -> list:
    """The cell's TPU chips; raises NoChip if JAX finds none or too few."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}") from None
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is "
                     f"{devices[0].platform if devices else None!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def device_peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in "
                     f"bench/peaks.json (has {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Programs compiled or loaded from the compile cache, as
    `jax.monitoring` reports them."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


class Recorder:
    """Keeps every call of the fused forward (`batch_jax.forward_device` and
    `forward_device_stacked`) and of the stacked GP's fit and scoring
    (`gp._fit_stack`, `gp._score_stack`) while installed, for the check
    after the window.  Holding the returned device arrays adds no host sync.
    With `timed`, each GP call is also waited for and its wall time added
    to `wall_s["gp"]`: that syncs the host with the device after every GP
    call, so only traced runs time."""

    def __init__(self, jtlb, gp, timed: bool = False):
        self.jtlb, self.gp, self.timed = jtlb, gp, timed
        self.calls, self.gp_calls, self.gp_dtypes = [], [], []
        self.search = -1
        self.rows = []  # mapping rows per forward call, in order
        self.wall_s = {"gp": 0.0}
        self._orig = (jtlb.forward_device, jtlb.forward_device_stacked,
                      gp._fit_stack, gp._score_stack)

    def _gp(self, fn, *args):
        if not self.timed:
            return fn(*args)
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        self.wall_s["gp"] += time.perf_counter() - t0
        return out

    def _single(self, hw, mb, layer, **kw):
        out = self._orig[0](hw, mb, layer, **kw)
        self._keep([hw], [mb], [layer], out, False)
        return out

    def _stacked(self, hw, pools, layers, **kw):
        out = self._orig[1](hw, pools, layers, **kw)
        hws = list(hw) if isinstance(hw, (list, tuple)) else [hw] * len(pools)
        self._keep(hws, list(pools), list(layers), out, True)
        return out

    def _keep(self, hws, pools, layers, out, stacked):
        self.calls.append(checks.ForwardCall(self.search, hws, pools, layers,
                                             out, stacked))
        self.rows.append(sum(len(p) for p in pools))

    def _fit_stack(self, *args):
        out = self._gp(self._orig[2], *args)
        self.gp_dtypes.append((self.search, _dtypes(out)))
        return out

    def _score_stack(self, params, X, y, mask, feats, best, kind, acq_fn):
        idx, rows = self._gp(self._orig[3], params, X, y, mask, feats, best,
                             kind, acq_fn)
        self.gp_dtypes.append((self.search,
                               _dtypes([params, X, y, feats, rows])))
        self.gp_calls.append(checks.ScoreCall(self.search, kind, X, y, mask,
                                              feats, best, idx))
        return idx, rows

    def __enter__(self):
        self.jtlb.forward_device = self._single
        self.jtlb.forward_device_stacked = self._stacked
        self.gp._fit_stack = self._fit_stack
        self.gp._score_stack = self._score_stack
        return self

    def __exit__(self, *_):
        (self.jtlb.forward_device, self.jtlb.forward_device_stacked,
         self.gp._fit_stack, self.gp._score_stack) = self._orig


def _dtypes(tree) -> set:
    """The dtypes of a pytree's float leaves, as strings."""
    import jax

    return {str(leaf.dtype) for leaf in jax.tree.leaves(tree)
            if "float" in str(leaf.dtype)}


class Program:
    """The system under test, built from a configuration file."""

    def __init__(self, config: dict):
        src = os.path.join(ROOT, "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        from repro.core import CodesignConfig, CodesignEngine, gp
        from repro.jax_cache import enable_compile_cache
        from repro.timeloop import ConvLayer, HardwareConfig, EnergyTable
        from repro.timeloop import batch_jax

        self.cache_dir = enable_compile_cache()
        self.engine_cls = CodesignEngine
        self.batch_jax, self.gp = batch_jax, gp
        self.codesign = CodesignConfig.from_dict(config["codesign"])
        self.layers = [ConvLayer(**ly) for ly in config["layers"]]
        acc = config["accelerator"]
        hw, e = HardwareConfig(num_pes=acc["num_pes"]), EnergyTable()
        stated = {"num_pes": self.codesign.hw.num_pes,
                  "lb_budget": hw.lb_budget, "gb_entries": hw.gb_entries,
                  "dram_bandwidth": hw.dram_bandwidth,
                  "energy_pj": {k: getattr(e, k) for k in acc["energy_pj"]}}
        if stated != acc:
            raise SystemExit(f"the program's accelerator budget {stated} is "
                             f"not the configuration's {acc}")

    def search(self, seed: int, span=None):
        """One whole co-design search; `span(name)` wraps each step."""
        engine = self.engine_cls(dataclasses.replace(self.codesign, seed=seed))
        session = engine.session(self.layers)
        kind, more = "warmup", True
        while more:
            if span is None:
                more = session.step()
            else:
                with span(f"bench.step.{kind}"):
                    more = session.step()
            kind = "trial"
        return session.result()


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reads (`bench/metrics/<metric>.py`)."""
    trace: xplane.TraceSummary | None
    compiles_in_window: int
    window_wall_s: float        # the window's length by the host clock
    wall_s: dict                # wall seconds in each layer's calls, waited
    forward_rows: list          # mapping rows per forward call, in order
    forward_float_bytes: int
    peaks: dict
    notes: dict


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Window:
    start: float
    ends: list
    results: list
    recorder: Recorder
    compiles: int
    trace: xplane.TraceSummary | None


class Bench:
    """One cell, set up: its files, its chips and the program under test."""

    def __init__(self, workload: str, root: str = ROOT):
        found = load_cell(root, workload)
        self.cell, self.config = found["cell"], found["config"]
        self.mix, self.per_layer = found["traffic"], found["per_layer"]
        self.end_to_end = found["end_to_end"]
        self.devices = find_devices(self.cell["chips"])
        self.peaks = device_peaks(self.devices[0].device_kind)
        import jax.monitoring

        self.compiles = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        self.program = Program(self.config)
        self.checker = checks.Checker(self.config, found["reference"],
                                      found["surrogate_reference"])

    def warm_up(self) -> None:
        """One pass over the round: every program the window uses."""
        for seed in self.mix["round"]:
            self.program.search(seed)

    def window(self, rounds, seconds: float, trace: bool) -> Window:
        """The measured window (`traffic.closed_loop`), under the profiler
        when `trace`, which also times each layer's calls (`Recorder`)."""
        import jax

        results = []
        span = jax.profiler.TraceAnnotation if trace else None

        def one(i, seed):
            recorder.search = i
            if span is None:
                results.append(self.program.search(seed))
            else:
                with span("bench.search"):
                    results.append(self.program.search(seed, span))

        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        with Recorder(self.program.batch_jax, self.program.gp,
                      timed=trace) as recorder:
            if trace:
                xplane.start(trace_dir)
                with span(xplane.WINDOW_SPAN):
                    compiles0 = self.compiles.n
                    start, ends = traffic.closed_loop(one, rounds, seconds)
            else:
                compiles0 = self.compiles.n
                start, ends = traffic.closed_loop(one, rounds, seconds)
            compiles = self.compiles.n - compiles0
            if trace:
                jax.profiler.stop_trace()
        summary = None
        if trace:
            path, = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            summary = xplane.summarize(xplane.load(path))
            shutil.rmtree(trace_dir, ignore_errors=True)
        return Window(start, ends, results, recorder, compiles, summary)

    def per_layer_metrics(self, w: Window) -> tuple[dict, dict]:
        record = RunRecord(w.trace, w.compiles, w.ends[-1] - w.start,
                           dict(w.recorder.wall_s), w.recorder.rows,
                           _float_bytes(self.config["forward_dtype"]),
                           self.peaks, {})
        metrics = {}
        for metric, reader in self.per_layer:
            value = reader.read(record)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        return metrics, record.notes


def run(args, root: str = ROOT) -> dict:
    """Set up, measure, check; returns the result line's object."""
    bench = Bench(args.workload, root)
    bench.warm_up()
    w = bench.window(traffic.rounds(bench.mix, args.seed), args.seconds,
                     bool(args.trace))
    setup_s = w.start - T_START
    search_s = (w.ends[-1] - w.start) / len(w.ends)
    devices = bench.devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak(
                  devices[:bench.cell["chips"]])}
    out = {"correct": False, "attempted": len(w.ends), "failed": 0,
           "metrics": {}, "device": device}
    if args.trace:
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        out["metrics"], notes = bench.per_layer_metrics(w)
        notes["window_span_s"] = w.trace.span_s
        notes["window_traced_share"] = w.trace.window_s / w.trace.span_s
        out["breakdown"] = xplane.breakdown(w.trace)
        out["notes"] = notes
    else:
        values = {"search_s": search_s, "setup_s": setup_s}
        for metric in bench.end_to_end:
            out["metrics"][metric["name"]] = {"value": values[metric["name"]],
                                              "unit": metric["unit"]}

    t_check = time.perf_counter()
    verdict = bench.checker.judge(w.recorder, w.results)
    check_s = time.perf_counter() - t_check
    out["correct"] = verdict["correct"]
    out["failed"] = verdict["failed"]
    out["checks"] = verdict["checks"]
    print(f"window: {len(w.ends)} searches in {w.ends[-1] - w.start:.3f} s, "
          f"{w.compiles} programs built in the window, set-up "
          f"{setup_s:.3f} s, compile cache {bench.program.cache_dir}, "
          f"{verdict['rows']} forward rows and {verdict['picks']} GP picks "
          f"checked in {check_s:.1f} s", file=sys.stderr)
    for name, c in verdict["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return out


def _float_bytes(dtype: str) -> int:
    return {"float32": 4, "float64": 8, "bfloat16": 2}[dtype]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
