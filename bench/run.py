"""Benchmark entry: runs one cell on the chip and prints one JSON line.

    python3 bench/run.py --workload p125m-sync-tau8 --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine with a TPU. The cell's files are
found by name under ``bench/`` (see ``harness.py``). With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the window is
profiled and the result carries the per-layer metrics instead. A run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and prints
no result. The last lines on standard error, and the result's last key,
``checks``, give each number compared with the plain reference beside its
limit.
"""
from __future__ import annotations

import time

T_NOW = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

T0 = T_NOW - harness.process_age_s()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import repro.launch.train  # noqa: F401  (fails fast where the program is absent)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2

    out_dir = harness.CHECKOUT / "results" / "bench" / f"{cell.name}-seed{args.seed}"
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0, out_dir)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
