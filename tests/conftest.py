"""Test fixtures: run everything on a virtual 8-device CPU mesh.

This is the JAX analogue of the reference's "multi-node without a cluster"
trick (ref: /root/reference/README.md:119-144 — oversubscribing one node with
CUDA_VISIBLE_DEVICES partitions): XLA's host platform is told to expose 8
virtual CPU devices, so every sharding/collective path compiles and runs
without TPU hardware.
"""

import os

# Must be set before jax backends initialize. Force-override: tests run on
# the fake mesh whatever the session's JAX_PLATFORMS says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture
def chip_bench_root(tmp_path):
    """A synthetic ``BENCH_r01``–``r05`` set in the driver's record shape
    (``parsed.metric``/``value``), written under ``tmp_path / "bench"``:
    what the bench-index and regression-gate tests run on. Returns
    ``(root, values, copy_in)``; ``copy_in(name)`` adds one of the
    repository's own artifacts beside them."""
    import json
    import shutil

    root = tmp_path / "bench"
    root.mkdir()
    values = [2500.0, 2550.0, 2400.0, 2525.0, 2575.0]
    for i, v in enumerate(values, start=1):
        (root / f"BENCH_r{i:02d}.json").write_text(json.dumps({
            "n": i, "rc": 0,
            "parsed": {
                "metric": "resnet50_train_images_per_sec_per_chip",
                "value": v, "unit": "images/sec/chip",
                "vs_baseline": round(v / 400.0, 3),
            },
        }))

    def copy_in(name):
        repo = os.path.join(os.path.dirname(__file__), "..")
        shutil.copy(os.path.join(repo, name), root / name)

    return str(root), values, copy_in


@pytest.fixture(autouse=True)
def _reset_global_cfg():
    """Each test sees pristine config defaults."""
    from distribuuuu_tpu import config

    config.reset_cfg()
    yield
    config.reset_cfg()
