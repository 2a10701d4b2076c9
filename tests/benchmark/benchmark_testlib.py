"""Shared by the benchmark's tests: checkouts in a temp directory.

``make_root`` builds a directory that looks like a checkout to the harness:
its own ``BENCHMARK.json`` (the repo's, plus whatever entries a test adds), a
COPY of ``benchmark/`` (so a test can add files to it) and links to the
program. The harness finds its root from its own location, so running the
copy's ``run.py`` runs against the copy.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PENDING = os.path.join(REPO, "benchmark", "pending")


def make_root(tmp_path, extra_entries=()):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", "fixtures"),
    )
    for name in ("config", "distribuuuu_tpu"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for entries in extra_entries:
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            benchmark[key] += entries.get(key, [])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(benchmark, f)
    return root


def pending_entries():
    """The entries kept for a later benchmark PR (benchmark/pending/)."""
    out = []
    for name in sorted(os.listdir(PENDING)):
        with open(os.path.join(PENDING, name)) as f:
            out.append(json.load(f))
    return out


def start_run(root, *argv, devices=1):
    """Start ``benchmark/run.py`` of checkout ``root`` on the CPU."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    return subprocess.Popen(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )


def finish(process, timeout=240):
    out, err = process.communicate(timeout=timeout)
    return process.returncode, out, err
