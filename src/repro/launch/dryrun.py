import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the dry-run lowers against 512 fake HOST devices: on a TPU host JAX would
# otherwise take the chip and then fail to build the 512-device mesh
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run entry point.

Lowers + compiles the production program for every (architecture x input
shape) on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh, printing
memory_analysis / cost_analysis and writing JSON artifacts consumed by the
roofline benchmark (benchmarks/roofline.py) and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=[None, "train_4k", "prefill_32k", "decode_32k",
                             "long_500k"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--mode", default=None, choices=[None, "ddp", "fsdp"])
    ap.add_argument("--filter", default=None,
                    help="gradient filter for train_4k (default trimmed_mean)")
    ap.add_argument("--impl", default=None,
                    choices=[None, "fused", "gather", "pallas", "auto"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    # §Perf variant knobs
    ap.add_argument("--group-size", type=int, default=0,
                    help="median-of-means grouping [19] for train_4k")
    ap.add_argument("--agg-dtype", default="",
                    help="cast exchanged gradients (e.g. bfloat16)")
    ap.add_argument("--reshard", action="store_true",
                    help="reshard grad stacks before coordinate filters")
    ap.add_argument("--cache-layout", default="headdim",
                    choices=["headdim", "seq"],
                    help="decode KV-cache sharding layout")
    ap.add_argument("--remat", action="store_true",
                    help="per-layer activation checkpointing (train)")
    ap.add_argument("--moe-dispatch", action="store_true",
                    help="capacity-sharded MoE dispatch (prefill)")
    args = ap.parse_args()

    # imports AFTER the XLA_FLAGS pin
    from repro.configs import ASSIGNED_ARCHS
    from repro.launch.dryrun_lib import run_combo
    from repro.launch.input_specs import SHAPES
    from repro.training.step import ByzantineConfig

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    bz = None
    if (args.filter or args.impl or args.group_size or args.agg_dtype
            or args.reshard or args.remat):
        from repro.core.aggregators import make_spec
        bz = lambda multi: ByzantineConfig(
            n_agents=32 if multi else 16,
            f=7 if multi else 3,
            aggregator=make_spec(args.filter or "trimmed_mean",
                                 f=7 if multi else 3,
                                 impl=args.impl or "fused",
                                 n=32 if multi else 16),
            group_size=args.group_size or 1,
            agg_dtype=args.agg_dtype,
            reshard=args.reshard,
            remat=args.remat)

    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                try:
                    run_combo(arch, shape, multi, out_dir=args.out,
                              mode=args.mode,
                              bz=bz(multi) if bz else None, tag=args.tag,
                              skip_existing=args.skip_existing,
                              cache_layout=args.cache_layout,
                              moe_dispatch=args.moe_dispatch)
                except Exception as e:      # record, keep sweeping
                    import json as _json
                    import os as _os
                    mesh_name = "pod512" if multi else "pod256"
                    nm = f"{arch}_{shape}_{mesh_name}"
                    nm += f"_{args.tag}" if args.tag else ""
                    _os.makedirs(args.out, exist_ok=True)
                    with open(_os.path.join(args.out, nm + ".json"),
                              "w") as fh:
                        _json.dump({"arch": arch, "shape": shape,
                                    "mesh": mesh_name,
                                    "error": repr(e)[:2000]}, fh, indent=1)
                    print(f"[dryrun] ERROR {nm}: {repr(e)[:200]}")


if __name__ == "__main__":
    main()
