"""The plain reference against the program's NumPy float64 engine, and its
bfloat16 control against the float64 reference."""

import importlib.util
import json
import os

import ml_dtypes
import numpy as np
import pytest

from repro.timeloop import eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop.arch import sample_hardware_pool
from repro.timeloop.mapping import sample_constrained_batch
from repro.timeloop.workloads import ConvLayer

import checks
from conftest import BENCH

spec = importlib.util.spec_from_file_location(
    "reference_timeloop", os.path.join(BENCH, "references", "timeloop.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)

CONFIGS = ("resnet18-eyeriss168", "dqn-eyeriss168")


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def pool(rng, hw, layer, n=300):
    """Half valid rows, half raw constrained draws (partly invalid)."""
    raw = tlb.MappingBatch(*sample_constrained_batch(rng, hw, layer, n // 2))
    valid = tlb.sample_valid_pool(rng, hw, layer, n // 2)
    return raw if valid is None else tlb.concat([valid, raw])


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_numpy_engine(name):
    cfg = config(name)
    checker = checks.Checker(cfg, ref)
    rng = np.random.default_rng(1)
    hws = [eyeriss_168()] + sample_hardware_pool(rng, 5)
    for ly in cfg["layers"]:
        layer = ConvLayer(**ly)
        for hw in hws:
            mb = pool(rng, hw, layer)
            want = tlb.evaluate_batch(hw, mb, layer)
            got = checker._rows([hw], [layer.name], mb.factors, mb.order_gb,
                                mb.order_dram, [len(mb)], np.float64)
            np.testing.assert_array_equal(got["valid"], want["valid"])
            v = want["valid"]
            np.testing.assert_allclose(got["edp"][v], want["edp"][v],
                                       rtol=1e-12)
            assert np.isinf(got["edp"][~v]).all()


def test_bfloat16_control_departs():
    cfg = config("resnet18-eyeriss168")
    checker = checks.Checker(cfg, ref)
    rng = np.random.default_rng(2)
    layer = ConvLayer(**cfg["layers"][1])
    hw = eyeriss_168()
    mb = pool(rng, hw, layer)
    args = ([hw], [layer.name], mb.factors, mb.order_gb, mb.order_dram,
            [len(mb)])
    hi = checker._rows(*args, np.float64)
    lo = checker._rows(*args, ml_dtypes.bfloat16)
    both = hi["valid"] & lo["valid"]
    gap = np.abs(lo["edp"][both] - hi["edp"][both]) / hi["edp"][both]
    assert gap.max() > 10 * cfg["limits"]["forward_gap"]


def test_hardware_budget():
    cfg = config("resnet18-eyeriss168")
    checker = checks.Checker(cfg, ref)
    hw = eyeriss_168()
    assert ref.hardware_is_valid(checker._hw(hw), cfg["accelerator"])
    import dataclasses
    bad = dataclasses.replace(hw, lb_input=hw.lb_input + 200)
    assert not ref.hardware_is_valid(checker._hw(bad), cfg["accelerator"])
