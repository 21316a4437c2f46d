"""Fixtures of the benchmark's own tests, run by hand on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``tiny_root`` lays out a benchmark tree with one cell, ``tiny``: photon-125m's
configuration file cut to 2 layers of width 64 and a 500-token vocabulary,
under the two-client sync traffic at S = 64, with limits of its own,
``TINY_LIMITS``, set from readings at this size (``calibrate.py``, three
seeds): the program reads at most 1.8e-5 / 2.7e-4 / 1.5e-4 on
``first_loss_gap`` / ``pg_gap`` / ``median_change_gap``, and the fp8 control
at least 5.2e-4 / 2.6e-3 / 8.3e-4. The full cells' limits are set from chip
readings at their own size (see the workload files). The checks, readers and references are the benchmark's
own files.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

TINY_SEED = 3_000_000_017
TINY_LIMITS = {"first_loss_gap": 1e-4, "pg_gap": 1e-3, "median_change_gap": 4.2e-4}


def _dump(obj, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench")
    for d in ("checks", "end_to_end", "metrics"):
        shutil.copytree(BENCH / d, root / d)
    _dump({"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                   "source": "placeholder for tests on the CPU"}}, root / "peaks.json")
    cfg = json.loads((BENCH / "configs" / "photon-125m.json").read_text())
    cfg.update(n_layers=2, d_model=64, n_heads=4, d_ff=256, vocab_size=500,
               padded_vocab=512, max_seq_len=64)
    _dump(cfg, root / "configs" / "tiny.json")
    traffic = json.loads((BENCH / "traffic" / "sync-c2-b2.json").read_text())
    traffic["flags"].update({"--seq-len": 64, "--rounds": 40})
    _dump(traffic, root / "traffic" / "tiny.json")
    work = json.loads((BENCH / "workloads" / "p125m-sync-tau8.json").read_text())
    work.update(config="tiny", traffic="tiny", limits=TINY_LIMITS)
    _dump(work, root / "workloads" / "tiny.json")
    return root


@pytest.fixture(autouse=True)
def cpu_memory_peak(monkeypatch):
    """The CPU reports no allocator peak; the harness then reads 0 here."""
    import harness

    monkeypatch.setattr(harness, "memory_peak_bytes", lambda device: 0)


@pytest.fixture
def tiny_cell(tiny_root):
    import harness

    return harness.load_cell("tiny", tiny_root)


def run_tiny(cell, root: Path, out: Path, seconds: float = 0.0, check=None) -> dict:
    import time

    import harness

    return harness.run_cell(cell, TINY_SEED, seconds, False, time.perf_counter(),
                            out, root, check=check)
