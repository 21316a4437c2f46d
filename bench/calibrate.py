"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/calibrate.py --workload p125m-sync-tau8 --seeds 12 --faults 3 \
        --out results/bench/calibrate-p125m.jsonl

For each seed, in this one process: the program runs the cell's set-up rounds
and one window round through the harness, and the plain reference replays the
set-up rounds; the gaps between the two are the program's readings. On the
first ``--faults`` seeds the same comparison is also made for the control
(the reference computed in fp8, the precision below the configuration's
bfloat16), for the reference computed in bfloat16 (how far rounding alone
carries a run), and for two faults planted in the reference put in the
program's place: ``half_batch`` (the loss of each step taken over half of its
batch) and ``no_exchange`` (the server applies one client's delta instead of
the cohort's mean). A state left unchanged reads 1 on ``pg_gap`` without a
run. One JSON line per seed and side, with each side's round losses and leaf
norms beside the numbers the check compares.
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

SIDES = (("control", "fp8", None), ("bf16", "bf16", None),
         ("half_batch", "f32", "half_batch"), ("no_exchange", "f32", "no_exchange"))


def _line(side: str, got, ref, check) -> dict:
    return {"side": side, **check.compare(got, ref), "losses": got.losses,
            "reference_losses": ref.losses, "pg": got.pg, "reference_pg": ref.pg,
            "change": got.change, "reference_change": ref.change}


def calibrate(cell, seeds: int, first_seed: int, faults: int, out: Path, t0: float) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "a") as f:
        for i in range(seeds):
            seed = first_seed + 7919 * i
            check = harness.load_check(cell, seed)
            t = time.perf_counter()
            res = harness.run_cell(cell, seed, 0.0, False, t0,
                                   harness.CHECKOUT / "results" / "bench" / "calibrate",
                                   check=check)
            ref = check.reference
            lines = [dict(_line("program", check.program_readings(), ref, check),
                          memory_peak_bytes=res["device"]["memory_peak_bytes"])]
            if i < faults:
                for side, precision, fault in SIDES:
                    got = check.reference_readings(precision=precision, fault=fault)
                    lines.append(_line(side, got, ref, check))
            for line in lines:
                line.update(workload=cell.name, seed=seed)
                f.write(json.dumps(line) + "\n")
                print(json.dumps(line), flush=True)
            print(f"seed {seed}: {time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    calibrate(harness.load_cell(args.workload), args.seeds, args.first_seed,
              args.faults, Path(args.out), T_NOW - harness.process_age_s())
    return 0


if __name__ == "__main__":
    sys.exit(main())
