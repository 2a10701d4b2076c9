"""Compile-only rehearsal for the chip, without the chip.

Compiles each cell's real-size program with the installed XLA:TPU and Mosaic
for a ``v5e:2x2`` host that is described, not attached, and prints
``memory_analysis()`` per device, so that no chip call (least of all a
four-chip one) is spent finding a compile error or an out-of-memory. Nothing
runs: this says nothing about results or times.

    python benchmark/rehearse_compile.py                      # every cell
    python benchmark/rehearse_compile.py --workload resnet50.train_dp4 \\
        --set train_job.per_chip_batch=256                     # a sweep point

Each cell compiles in a child process whose CPU backend shows as many
devices as the cell has chips (the program sizes its mesh from
``jax.device_count()``). The program's kernel tier asks the live backend and
would take its CPU branch, so the child steers it to the compiled Pallas
update, as ``auto`` resolves on the chip.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPOLOGY = "v5e:2x2"


def compile_cell(workload: str, sets: list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from benchmark.harness import cli
    from benchmark.harness.discovery import Catalog
    from distribuuuu_tpu.ops import pallas as kernel_tier

    catalog = Catalog()
    cell = catalog.cell(workload)
    kernel_tier.interpret_mode = lambda: False  # compile Mosaic, as on the chip
    overrides = dict(cell.config["program"]["overrides"], **{"KERNELS.OPT_UPDATE": "pallas"})
    argv = ["--workload", workload,
            "--set", "program.overrides=" + json.dumps(overrides),
            *sum((["--set", s] for s in sets), [])]
    run = cli.Run(catalog, cell, argv, time.perf_counter())
    driver = catalog.driver(run.traffic["driver"])
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY
    ).devices[:cell.chips]
    # a compile-only executable cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    t0 = time.perf_counter()
    for name, compiled in driver.compile_only(run, devices).items():
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        hlo = compiled.as_text()
        print(
            f"{workload} {name} on {cell.chips} x {TOPOLOGY} chip(s), "
            f"{run.section('train_job')}: compiled in "
            f"{time.perf_counter() - t0:.0f} s; per device: arguments "
            f"{m.argument_size_in_bytes / 2**30:.2f} GiB, outputs "
            f"{m.output_size_in_bytes / 2**30:.2f} GiB (aliased "
            f"{m.alias_size_in_bytes / 2**30:.2f}), temporaries "
            f"{m.temp_size_in_bytes / 2**30:.2f} GiB, total "
            f"{total / 2**30:.2f} GiB; all-reduce ops "
            f"{hlo.count(' all-reduce(') + hlo.count(' all-reduce-start(')}, "
            f"Mosaic calls {hlo.count('tpu_custom_call')}",
            flush=True,
        )


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", action="append", default=[])
    p.add_argument("--set", action="append", default=[])
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    if args.child:
        compile_cell(args.workload[0], args.set)
        return 0
    from benchmark.harness.discovery import Catalog

    catalog = Catalog()
    rc = 0
    for entry in catalog.benchmark["workloads"]:
        if args.workload and entry["name"] not in args.workload:
            continue
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={entry['chips']}",
        )
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--workload", entry["name"], *sum((["--set", s] for s in args.set), [])]
        rc |= subprocess.run(cmd, env=env, cwd=ROOT).returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
