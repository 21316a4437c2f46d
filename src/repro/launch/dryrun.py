import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable (e)).

Lowers + compiles every (architecture × input shape) against the production meshes —
single-pod (16, 16) = 256 chips and multi-pod (2, 16, 16) = 512 chips — on 512
placeholder host devices, printing memory_analysis() (proves it fits) and
cost_analysis() (FLOPs/bytes for §Roofline), plus the HLO collective traffic.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch assigned --shape all --multi-pod both
"""
import argparse
import json
import time
import traceback


def main() -> None:
    from repro.core.compression import UPLINK_SCHEMES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="assigned", help="arch id | 'assigned' | comma list")
    ap.add_argument("--shape", default="all", help="shape name | 'all' | comma list")
    ap.add_argument("--multi-pod", default="no", choices=["no", "yes", "both"])
    ap.add_argument("--tau-lowered", type=int, default=4)
    ap.add_argument("--train-mode", default="federated", choices=["federated", "centralized", "both"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--pseudo-grad-dtype", default="float32")
    ap.add_argument("--uplink", default="float32",
                    choices=list(UPLINK_SCHEMES),
                    help="compressed-uplink codec for the federated round: the "
                         "encoded-delta dtypes are carried through the mesh "
                         "lowering (residual inputs sharded like the client axis)")
    ap.add_argument("--topk-fraction", type=float, default=0.05)
    ap.add_argument("--cohort-tile", type=int, default=None,
                    help="lower the federated step as ONE TILE of a streamed "
                         "cohort (run_client_tile, client width = tile): the "
                         "population/cohort sizes never enter the lowering, "
                         "so per-device memory is flat in P (asserted by the "
                         "slow dry-run test)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="", help="suffix for result filenames (perf iters)")
    args = ap.parse_args()

    import jax

    from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step
    from repro.roofline.analysis import analyze_compiled

    archs = ASSIGNED_ARCHS if args.arch == "assigned" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    pods = {"no": [False], "yes": [True], "both": [False, True]}[args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            ok, why = cfg.supports_shape(shape_name)
            if not ok:
                print(f"SKIP  {arch} x {shape_name}: {why}")
                continue
            modes = ["federated"]
            if INPUT_SHAPES[shape_name].kind == "train":
                modes = {
                    "federated": ["federated"],
                    "centralized": ["centralized"],
                    "both": ["federated", "centralized"],
                }[args.train_mode]
            else:
                modes = [None]
            for multi_pod in pods:
                mesh = make_production_mesh(multi_pod=multi_pod)
                chips = mesh.size
                for mode in modes:
                    tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
                    if mode:
                        tag += f"__{mode}"
                    if args.tag:
                        tag += f"__{args.tag}"
                    t0 = time.perf_counter()
                    try:
                        kw = {}
                        if INPUT_SHAPES[shape_name].kind == "train":
                            kw = dict(
                                tau_lowered=args.tau_lowered,
                                remat=not args.no_remat,
                                mode=mode,
                                pseudo_grad_dtype=args.pseudo_grad_dtype,
                                uplink=args.uplink,
                                topk_fraction=args.topk_fraction,
                                cohort_tile=args.cohort_tile,
                            )
                        with mesh:
                            step = build_step(cfg, shape_name, mesh, **kw)
                            lowered = step.fn.lower(*step.args)
                            compiled = lowered.compile()
                            mem = compiled.memory_analysis()
                            print(f"== {tag} ==")
                            print(f"  memory_analysis: {mem}")
                            cost = compiled.cost_analysis()
                            print(
                                "  cost_analysis: flops=%.3e bytes=%.3e"
                                % (cost.get("flops", 0), cost.get("bytes accessed", 0))
                            )
                            report = analyze_compiled(
                                tag, compiled, chips, model_flops=step.model_flops,
                                extra={"meta": step.meta, "arch": arch,
                                       "shape": shape_name, "multi_pod": multi_pod,
                                       "mode": mode or "serve",
                                       "compile_s": time.perf_counter() - t0},
                            )
                            print(
                                "  roofline: compute=%.4fs memory=%.4fs collective=%.4fs -> %s"
                                % (report.t_compute, report.t_memory,
                                   report.t_collective, report.bottleneck)
                            )
                            print(f"  collectives: {report.collective_counts}")
                            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                                json.dump(report.to_dict(), f, indent=2, default=str)
                    except Exception:
                        n_fail += 1
                        print(f"FAIL  {tag}")
                        traceback.print_exc()
                    finally:
                        print(f"  [{time.perf_counter() - t0:.1f}s]", flush=True)

    print(f"\ndone; failures: {n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
