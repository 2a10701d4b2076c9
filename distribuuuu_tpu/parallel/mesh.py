"""Device-mesh bootstrap: the TPU-native replacement for process groups.

The reference initializes an NCCL process group from one of three bootstrap
modes — launcher env vars, Slurm derivation, or explicit TCP rendezvous
(ref: /root/reference/distribuuuu/utils.py:19-51, tutorial/mnmc_ddp_mp.py:41-66).
Here the same discovery logic feeds ``jax.distributed.initialize`` (one
process per *host*, all local chips attached), and the "process group" is a
``jax.sharding.Mesh`` over every chip in the slice. Collectives are not
called by user code: they are compiled into the step function by XLA from
sharding annotations and ride ICI within a slice / DCN across slices.

Mesh axes (configured by ``cfg.MESH``):
  - ``data``   — data parallelism (batch sharding; DDP equivalent)
  - ``model``  — tensor/model parallelism (params/heads sharding)
  - ``seq``    — sequence/context parallelism (ring attention)
  - ``pipe``   — GPipe pipeline parallelism (parallel/pp.py)
  - ``expert`` — dedicated MoE dispatch axis (composes EP with TP)
The reference only exercises data parallelism; the extra axes are
first-class so larger workloads shard without restructuring. Any stanza
is validated/classified by the partition-layer topology registry
(parallel/partition/topology.py) before a mesh is built.
"""

from __future__ import annotations

import functools
import os
import subprocess

import jax
import numpy as np
from jax.sharding import Mesh

_initialized = False
_DEFAULT_COORD_PORT = 29566  # matches the reference's default port (utils.py:35)

MESH_AXES = ("data", "model", "seq", "pipe", "expert")


def _slurm_env():
    """Derive process topology from Slurm env (ref: utils.py:26-40)."""
    proc_id = int(os.environ["SLURM_PROCID"])
    n_procs = int(os.environ["SLURM_NTASKS"])
    node_list = os.environ["SLURM_NODELIST"]
    # First hostname in the allocation is the coordinator.
    addr = subprocess.getoutput(
        f"scontrol show hostname {node_list} | head -n1"
    ).strip()
    return addr, n_procs, proc_id


def apply_backend_flags(deterministic: bool = False) -> None:
    """Append backend flags to XLA_FLAGS before backend initialization.

    The reference's cuDNN determinism toggle (ref: utils.py:64-68) maps here:
    XLA:TPU compilation is deterministic by default; the GPU-only flag is
    appended for completeness when running this framework on GPU. Must be
    called before any jax API touches the backend.
    """
    if deterministic:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_gpu_deterministic_ops" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_gpu_deterministic_ops=true"
            ).strip()


def apply_platform(platform: str) -> None:
    """Honor ``cfg.DEVICE.PLATFORM`` ("auto" keeps the ambient platform:
    ``JAX_PLATFORMS`` if set, else jax's own choice). Must run before
    any jax backend use."""
    if platform and platform != "auto":
        jax.config.update("jax_platforms", platform)


def describe_devices() -> str:
    """``platform=… device_kind=… devices=N`` of the live backend — the
    start-up lines of the trainer and the serving engines carry it, so a
    run that fell back to the CPU says so in its first line."""
    dev = jax.devices()
    return (
        f"platform={dev[0].platform} device_kind={dev[0].device_kind} "
        f"devices={len(dev)}"
    )


def setup_distributed(port: int | None = None) -> None:
    """Initialize multi-host JAX if a multi-process launch is detected.

    Bootstrap modes, mirroring the reference's three paths (ref:
    utils.py:19-51):
      (a) explicit env: ``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID``
          (JAX-native) or torch-launcher-style ``MASTER_ADDR``/``WORLD_SIZE``/
          ``RANK``;
      (b) Slurm: derived from ``SLURM_PROCID``/``SLURM_NTASKS``/
          ``SLURM_NODELIST`` via scontrol;
      (c) single-process (the default): no-op — every local chip is already
          visible, which is JAX's analogue of single-node DataParallel.
    Safe to call multiple times; only the first call initializes.
    """
    global _initialized
    if _initialized:
        return
    # Multi-process detection uses env vars ONLY: jax.distributed.initialize
    # must run before anything initializes the XLA backend, so no jax API
    # (even jax.process_count()) may be touched on the way in.
    coord_port = port or int(os.environ.get("COORDINATOR_PORT", _DEFAULT_COORD_PORT))
    multi = (
        "COORDINATOR_ADDRESS" in os.environ
        or ("SLURM_PROCID" in os.environ
            and int(os.environ.get("SLURM_NTASKS", "1")) > 1)
        or ("MASTER_ADDR" in os.environ
            and int(os.environ.get("WORLD_SIZE", "1")) > 1)
    )
    if multi:
        # The CPU client ships its cross-process collectives behind a flag
        # that defaults to "none", and a none-collectives client REFUSES
        # every computation spanning processes ("Multiprocess computations
        # aren't implemented on the CPU backend") — which silently breaks
        # the whole multi-process drill suite on CPU hosts. Select gloo
        # before the backend initializes; harmless on TPU (the option only
        # shapes CPU client creation).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if "COORDINATOR_ADDRESS" in os.environ:
        jax.distributed.initialize()  # JAX reads its own env contract
    elif "SLURM_PROCID" in os.environ and int(os.environ.get("SLURM_NTASKS", "1")) > 1:
        addr, n_procs, proc_id = _slurm_env()
        jax.distributed.initialize(
            coordinator_address=f"{addr}:{coord_port}",
            num_processes=n_procs,
            process_id=proc_id,
        )
    elif "MASTER_ADDR" in os.environ and int(os.environ.get("WORLD_SIZE", "1")) > 1:
        jax.distributed.initialize(
            coordinator_address=f"{os.environ['MASTER_ADDR']}:{coord_port}",
            num_processes=int(os.environ["WORLD_SIZE"]),
            process_id=int(os.environ["RANK"]),
        )
    _initialized = True


def get_rank() -> int:
    """Global process index (≙ dist.get_rank() at host granularity)."""
    return jax.process_index()


def get_world_size() -> int:
    """Number of host processes (≙ dist.get_world_size() over hosts)."""
    return jax.process_count()


def get_local_rank() -> int:
    """Index of this process among processes on the same node."""
    return int(os.environ.get("LOCAL_RANK", 0))


def is_primary() -> bool:
    """True on the logging/checkpointing process (≙ rank == 0 gates)."""
    return jax.process_index() == 0


def data_process_groups(mesh=None) -> tuple[int, int]:
    """``(data_rank, n_data_groups)`` for the host data pipeline.

    In the reference's pure-DP world every process owns a distinct slice
    of the batch, so ``(process_index, process_count)`` is the sampler
    shard (ref: utils.py:141-143). Once the model/pipe axes span
    *processes* (e.g. a 2×2 data×model mesh over 4 single-device hosts),
    processes in the same data row must load IDENTICAL data — their
    devices hold the same batch shard. This derives the data-group index
    from the mesh's device→process layout: processes whose devices cover
    the same set of data-axis rows form one group; samplers shard by
    group, not by process. Falls back to the classic (rank, world) in
    single-process runs and degenerates to exactly that whenever each
    process owns its own data rows.
    """
    if jax.process_count() == 1:
        return 0, 1
    if mesh is None:
        from distribuuuu_tpu.config import cfg

        mesh = mesh_from_cfg(cfg)
    return _data_groups_of_mesh(mesh)


@functools.lru_cache(maxsize=8)
def _data_groups_of_mesh(mesh) -> tuple[int, int]:
    # pure in the mesh (and this process's index) — cached because the
    # sharded-batch placement path calls it every step
    rows_by_proc: dict[int, set] = {}
    for idx, dev in np.ndenumerate(mesh.devices):
        rows_by_proc.setdefault(dev.process_index, set()).add(idx[0])
    keys = {p: tuple(sorted(s)) for p, s in rows_by_proc.items()}
    distinct = sorted(set(keys.values()))
    mine = keys.get(jax.process_index())
    if mine is None or any(
        a != b and set(a) & set(b) for a in distinct for b in distinct
    ):
        # a process outside the mesh, or groups that PARTIALLY overlap
        # data rows (a layout the host pipeline cannot feed correctly)
        raise ValueError(
            f"mesh device→process layout does not partition the data axis "
            f"into clean per-process-group row sets: {sorted(keys.items())}"
        )
    return distinct.index(mine), len(distinct)


def resolve_axis_sizes(
    sizes: list[int] | tuple[int, ...], n_devices: int
) -> list[int]:
    """Resolve ``-1``/``0`` wildcard entries against ``n_devices``.

    ``-1`` (and ``0``, accepted everywhere a size-1 axis is meant) on
    exactly one axis means "all remaining devices". The resolved product
    must equal the device count. Shared by mesh construction and the
    partition-layer topology registry, so stanza validation and the mesh
    actually built can never disagree on the resolved shape."""
    sizes = [1 if s == 0 else s for s in sizes]
    n_auto = sum(1 for s in sizes if s == -1)
    if n_auto > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {sizes}")
    fixed = int(np.prod([s for s in sizes if s != -1]))
    if fixed <= 0 or n_devices % fixed != 0:
        raise ValueError(
            f"Mesh axes {sizes} do not divide device count {n_devices}"
        )
    sizes = [n_devices // fixed if s == -1 else s for s in sizes]
    if int(np.prod(sizes)) != n_devices:
        raise ValueError(
            f"Mesh {dict(zip(MESH_AXES, sizes))} uses {int(np.prod(sizes))} "
            f"devices but {n_devices} are available"
        )
    return sizes


def build_mesh(
    data: int = -1, model: int = 1, seq: int = 1, pipe: int = 1,
    expert: int = 1, devices=None
) -> Mesh:
    """Build the global device mesh with axes
    ``(data, model, seq, pipe, expert)``.

    ``-1`` on exactly one axis means "all remaining devices". The total must
    divide the device count evenly. With defaults this is pure data
    parallelism over every chip — the reference's DDP topology.
    """
    devices = jax.devices() if devices is None else devices
    sizes = resolve_axis_sizes([data, model, seq, pipe, expert], len(devices))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, MESH_AXES)


def mesh_from_cfg(cfg, devices=None) -> Mesh:
    """Build the mesh described by ``cfg.MESH``."""
    return build_mesh(
        data=cfg.MESH.DATA,
        model=cfg.MESH.MODEL,
        seq=cfg.MESH.SEQ,
        pipe=cfg.MESH.PIPE,
        expert=cfg.MESH.get("EXPERT", 1),
        devices=devices,
    )
