"""Records a short traced window of a tiny sync cell, for ``bench/tests``.

    python3 bench/record_tiny_trace.py --out <dir>

Run from the root of a checkout on a host with one TPU v5e. The cell is
photon-125m's configuration cut to 2 layers of width 128 and a 500-token
vocabulary, two clients of 2 sequences of 128 tokens, τ = 2, one eval batch a
round. The harness runs it with ``--trace 1`` and a 0.1 s window; this script
then writes the window's profile, gzipped, as ``<name>.xplane.pb.gz`` and the
scope table of the programs that ran (``repro.obs.programs.op_scopes()``, cut
to the instructions that appear in the profile) as ``<name>.scopes.json.gz``.
"""
from __future__ import annotations

import time

T_NOW = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

T0 = T_NOW - harness.process_age_s()
SEED = 3_000_000_019


def tiny_cell() -> harness.Cell:
    cfg = json.loads((BENCH / "configs" / "photon-125m.json").read_text())
    cfg.update(n_layers=2, d_model=128, n_heads=4, d_ff=512, vocab_size=500,
               padded_vocab=512, max_seq_len=128, local_steps=2)
    traffic = json.loads((BENCH / "traffic" / "sync-c2-b2.json").read_text())
    traffic["flags"].update({"--seq-len": 128, "--rounds": 400})
    work = json.loads((BENCH / "workloads" / "p125m-sync-tau8.json").read_text())
    return harness.Cell("tiny-sync", work, cfg, traffic)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--name", default="tiny-sync-v5e-spans")
    args = ap.parse_args(argv)

    from repro.obs import programs

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    run_dir = Path(tempfile.mkdtemp())
    result = harness.run_cell(tiny_cell(), SEED, 0.1, True, T0, run_dir)
    print(json.dumps({k: result[k] for k in ("correct", "attempted", "metrics", "device")}))

    (path,) = glob.glob(str(run_dir / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    raw = Path(path).read_bytes()
    (out / f"{args.name}.xplane.pb.gz").write_bytes(gzip.compress(raw))

    from jax.profiler import ProfileData

    seen = set()
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                seen.update(ev.name.split(" = ", 1)[0].lstrip("%") for ev in line.events)
    table = {mod: {k: v for k, v in ops.items() if k in seen}
             for mod, ops in programs.op_scopes().items()}
    (out / f"{args.name}.scopes.json.gz").write_bytes(
        gzip.compress(json.dumps(table, indent=0, sort_keys=True).encode()))
    print(f"wrote {args.name}: {len(raw)} bytes of profile, "
          f"{ {k: len(v) for k, v in table.items()} } scoped instructions", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
