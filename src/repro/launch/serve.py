"""Serving launcher: lower + AOT-compile the P (prefill) and D (decode)
programs for an assigned architecture on the production mesh, then run a
local functional demo of the disaggregated flow on a reduced config.

On real hardware each pod runs this under its own jax.distributed
initialization; on this container the compile path is the multi-pod
dry-run (see dryrun.py) and ``--demo`` exercises the same code on a small
model with real numerics.

  python -m repro.launch.serve --arch qwen3-4b --shape decode_32k
  python -m repro.launch.serve --demo
"""
import argparse
import os


def compile_programs(arch: str, shape: str, multi_pod: bool) -> None:
    # dry run only: the production mesh lowers onto 512 placeholder host
    # devices (set before JAX is first imported; see dryrun.py for the two
    # disabled passes). The served path compiles with default passes.
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=512 "
        "--xla_disable_hlo_passes=while-loop-invariant-code-motion,"
        "while-loop-expensive-invariant-code-motion")
    from repro.launch.cells import get_cell
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (make_prefill_artifacts,
                                    make_serve_artifacts)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = get_cell(arch, shape)
    if cell.skip:
        print(f"[skip] {cell.name}: {cell.skip}")
        return
    arts = []
    if cell.mode in ("prefill", "decode"):
        arts.append(make_prefill_artifacts(
            get_cell(arch, "prefill_32k"), mesh))
        arts.append(make_serve_artifacts(
            get_cell(arch, "decode_32k"), mesh))
    for art in arts:
        compiled = art.lower().compile()
        ma = compiled.memory_analysis()
        tot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"[ok] {art.name}: compiled for {mesh.devices.size} chips, "
              f"{tot/2**30:.2f} GiB/chip")


def demo(connector: str = "inproc", two_process: bool = False,
         num_p: int = None, num_d: int = None, plan: bool = False,
         prefix_cache: bool = False) -> None:
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..", "..", "..")
    cmd = [sys.executable,
           os.path.join(root, "examples", "serve_disagg.py"),
           "--requests", "8", "--max-new", "8",
           "--connector", connector]
    if two_process:
        cmd.append("--two-process")
    if num_p is not None:
        cmd += ["--num-p", str(num_p)]
    if num_d is not None:
        cmd += ["--num-d", str(num_d)]
    if plan:
        cmd.append("--plan")
    if prefix_cache:
        cmd.append("--prefix-cache")
    subprocess.run(cmd, check=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--connector", default="inproc",
                    choices=["inproc", "shm", "rdma"],
                    help="KV-transport backend for the --demo serving loop")
    ap.add_argument("--two-process", action="store_true",
                    help="--demo only: run the P and D engines in separate "
                         "OS processes (requires --connector shm)")
    ap.add_argument("--num-p", type=int, default=None,
                    help="--demo only: prefill worker processes "
                         "(multi-process runtime; requires --connector shm)")
    ap.add_argument("--num-d", type=int, default=None,
                    help="--demo only: decode worker processes "
                         "(multi-process runtime; requires --connector shm)")
    ap.add_argument("--plan", action="store_true",
                    help="--demo only: size the topology with the planner "
                         "(plan_deployment → to_cluster_spec)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="--demo only: enable the shared-prefix KV cache "
                         "(prefill-compute and wire-byte skipping plus "
                         "cache-aware D routing)")
    args = ap.parse_args()
    if args.demo:
        demo(args.connector, args.two_process, args.num_p, args.num_d,
             args.plan, args.prefix_cache)
    else:
        compile_programs(args.arch, args.shape, args.multi_pod)


if __name__ == "__main__":
    main()
