"""Every module under `src/repro` imports under the installed jax.

A jax API that a release removes (as `jax.experimental.enable_x64` went in
0.9) then fails here, one test per module, instead of on the chip.
"""

import importlib
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
MODULES = sorted(
    ".".join(p.relative_to(SRC).with_suffix("").parts[:-1]
             if p.name == "__init__.py"
             else p.relative_to(SRC).with_suffix("").parts)
    for p in (SRC / "repro").rglob("*.py"))


def test_module_list_covers_the_package():
    assert "repro.core.gp" in MODULES and "repro.models.layers" in MODULES


@pytest.mark.parametrize("module", MODULES)
def test_module_imports(module):
    importlib.import_module(module)
