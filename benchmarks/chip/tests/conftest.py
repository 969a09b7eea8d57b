"""Tests of the chip benchmark, run on the CPU by path:

    python -m pytest benchmarks/chip/tests

(the repository's ``testpaths`` is ``tests/``, so the tier-1 run does not
collect them). Cells run here at a tiny size, from a copy of the benchmark's
files whose configurations are cut down (:func:`tiny_tree`).
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[3]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CONFIG = dict(n_per_shard=1024, dim=32, batch=32, delta_capacity=64,
                   degree=16, build_wave=64)
TINY_TRAFFIC = dict(pool=128, outstanding=64, rate_qps=150)


def copy_tree(dst: Path) -> Path:
    """The benchmark's files (``BENCHMARK.json`` and ``benchmarks/chip``)
    under ``dst``."""
    shutil.copytree(ROOT / "benchmarks" / "chip", dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__",
                                                  "tests", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def shrink(tree: Path) -> Path:
    """Cut every configuration and traffic mix of ``tree`` to a size the
    CPU runs in seconds (widths and loads only; relations, interval laws,
    selectivities and limits stay)."""
    home = tree / "benchmarks" / "chip"
    for sub, upd in (("configs", TINY_CONFIG), ("traffic", TINY_TRAFFIC)):
        for f in (home / sub).glob("*.json"):
            d = json.loads(f.read_text())
            d.update({k: v for k, v in upd.items() if k in d})
            f.write_text(json.dumps(d))
    return tree


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    return shrink(copy_tree(tmp_path_factory.mktemp("bench")))


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    from benchmarks.chip import harness

    monkeypatch.setattr(harness, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")
