"""A whole run off the chip at a size a test can hold: the chip lookup is
skipped, the rest of the run is the harness's own.  A sound run is correct;
the bfloat16 control and each fault planted in the timed path are not."""

import argparse
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import gp, nested
from repro.timeloop import batch_jax

import run
from conftest import ROOT

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def tiny(monkeypatch):
    """dqn.search cut to a few seconds: 36 inner trials (30 warmup, so
    the GP fits 30 to 36 rows as at the cell's size) over pools of 30.  Off
    the chip the forward runs `jnp` in float64."""
    found = run.load_cell(ROOT, "dqn.search")
    cfg = found["config"]
    cfg["codesign"]["engine"]["pallas_mode"] = "jnp"
    cfg["codesign"]["sw"].update(n_trials=36, n_warmup=30, pool_size=30)
    cfg["forward_dtype"] = "float64"
    found["traffic"] = {"loop": "closed", "clients": 1, "round": [11, 12]}
    monkeypatch.setattr(run, "load_cell", lambda root, workload: found)
    monkeypatch.setattr(run, "find_devices", lambda chips: jax.devices())
    monkeypatch.setattr(run, "device_peaks", lambda kind: PEAKS)
    return found


def run_once(seed=4294967311):
    args = argparse.Namespace(workload="dqn.search", seed=seed, seconds=0.0,
                              trace=0)
    return run.run(args)


def test_sound_run_is_correct(tiny, capsys):
    out = run_once()
    assert out["correct"] is True
    assert out["attempted"] == 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"search_s", "setup_s"}
    assert out["metrics"]["search_s"]["value"] > 0
    for check in out["checks"].values():
        assert check["value"] <= check["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert all(line.startswith("check ") for line in err[-7:])
    json.dumps(out)


def test_control_is_not_correct(tiny):
    bench = run.Bench("dqn.search")
    w = bench.window([[11]], 0.0, trace=False)
    sound = bench.checker.judge(w.recorder, w.results)
    control = bench.checker.judge(w.recorder, w.results, "control")
    assert sound["correct"] and not control["correct"]
    checks = control["checks"]
    assert checks["forward_gap"]["value"] > checks["forward_gap"]["limit"]
    assert checks["search_gap"]["value"] > checks["search_gap"]["limit"]
    assert checks["gp_pick_gap"]["value"] > checks["gp_pick_gap"]["limit"]


def _forward_fault(fault):
    orig = batch_jax.forward_device_stacked

    def broken(*args, **kw):
        out = dict(orig(*args, **kw))
        with jax.enable_x64(True):
            if fault == "answer_altered":
                out["edp"] = out["edp"] * 1.001
            else:  # half of the runs left out: their rows come back invalid
                half = out["valid"].shape[0] // 2
                out["valid"] = out["valid"].at[half:].set(False)
                out["edp"] = out["edp"].at[half:].set(np.inf)
        return out
    return broken


@pytest.mark.parametrize("fault,check", [
    ("answer_altered", "forward_gap"),
    ("half_left_out", "mask_mismatch"),
])
def test_forward_fault_is_caught(tiny, monkeypatch, fault, check):
    monkeypatch.setattr(batch_jax, "forward_device_stacked",
                        _forward_fault(fault))
    out = run_once()
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_altered_search_answer_is_caught(tiny, monkeypatch):
    orig = nested.evaluate

    def altered(hw, m, layer):
        return dataclasses.replace(orig(hw, m, layer),
                                   edp=orig(hw, m, layer).edp * 1.001)
    monkeypatch.setattr(nested, "evaluate", altered)
    out = run_once()
    assert out["correct"] is False
    assert out["checks"]["search_gap"]["value"] > 1e-4


def test_precision_other_than_stated_is_caught(tiny):
    tiny["config"]["forward_dtype"] = "float32"
    out = run_once()
    assert out["correct"] is False
    assert out["checks"]["dtype_mismatch"]["value"] > 0


def _gp_fault(fault):
    """The stacked GP broken where it works: half of a fit's runs left out
    (they keep their starting hyperparameters), or a scoring's pick moved
    to another candidate."""
    fit, score = gp._fit_stack, gp._score_stack

    def broken_fit(params, *args):
        out = fit(params, *args)
        half = len(out["mean_const"]) // 2
        with jax.enable_x64(True):
            return {k: v.at[half:].set(params[k][half:])
                    for k, v in out.items()}

    def broken_score(*args):
        idx, rows = score(*args)
        return (idx + 1) % args[4].shape[1], rows

    return ("_fit_stack", broken_fit) if fault == "half_left_out" else (
        "_score_stack", broken_score)


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_gp_fault_is_caught(tiny, monkeypatch, fault):
    monkeypatch.setattr(gp, *_gp_fault(fault))
    out = run_once()
    assert out["correct"] is False
    check = out["checks"]["gp_pick_gap"]
    assert check["value"] > check["limit"]


def test_gp_precision_other_than_stated_is_caught(tiny):
    tiny["config"]["surrogate"]["dtype"] = "float32"
    out = run_once()
    assert out["correct"] is False
    assert out["checks"]["gp_spec_mismatch"]["value"] > 0


def test_gp_wall_share_covers_the_window(tiny):
    """A traced run times every stacked GP call to its end; the reader
    gives their share of the window, and nothing without them."""
    import time

    bench = run.Bench("dqn.search")
    bench.warm_up()
    readers = {m["name"]: r for m, r in bench.per_layer}
    with run.Recorder(bench.program.batch_jax, bench.program.gp,
                      timed=True) as rec:
        t0 = time.perf_counter()
        bench.program.search(11)
        window = time.perf_counter() - t0
    record = run.RunRecord(None, 0, window, rec.wall_s, rec.rows, 8, {}, {})
    share = readers["gp_wall_share.search"].read(record)
    assert 0 < share < 100
    assert share == pytest.approx(100 * rec.wall_s["gp"] / window)
    record.wall_s = {"gp": 0.0}
    assert readers["gp_wall_share.search"].read(record) is None
