"""Performance attribution from XLA's own cost model (ISSUE 8 tentpole).

Telemetry so far measured *when* (spans) and *how often* (counters); this
module measures *why*: after a step program is built, XLA's
``cost_analysis()`` (flops, bytes accessed, transcendentals — computed
from the HLO itself, so it never drifts from the model the way a
hand-maintained FLOP table does) and ``memory_analysis()``
(argument/output/temp/generated bytes of the compiled executable) are
extracted and emitted as three schema-declared record kinds:

    cost.step      per-optimizer-step flops / bytes accessed (+ the peak
                   table resolution, so post-mortem consumers need no jax)
    cost.memory    the executable's HBM footprint vs device capacity →
                   headroom %
    cost.roofline  arithmetic intensity vs the device ridge point →
                   compute- or memory-bound verdict

Derived metrics (consumed by tools/run_report.py's MFU section, the live
monitor's ``mfu-regression`` / ``hbm-headroom-low`` rules, bench.py, and
tools/costmodel_report.py's committed COSTMODEL_*.json ledger):

* **measured MFU** — XLA flops ÷ measured step time ÷ device peak, per
  the shared ``DEVICE_PEAKS`` table (bench.py's former ``PEAK_BF16``
  moved here; bench now cross-checks its hand table against this and
  records ``flops_drift_pct``).
* **roofline position** — intensity = flops / bytes_accessed vs ridge =
  peak_flops / peak_bandwidth.
* **HBM ledger** — live bytes (arguments + outputs − aliased + temps +
  generated code) vs capacity → headroom %.

Cost comes from ``Lowered.cost_analysis()`` — tracing only, no second
XLA compile. Memory needs the compiled executable: ``capture_step``
(the trainer hook) AOT-compiles the lowered step when
``TELEMETRY.COSTMODEL_MEMORY`` is on (one extra startup compile per
distinct step program — the only cost of the whole plane), while
``capture_compiled`` (the serving hook) reads an executable the caller
already built, for free.

Graceful degradation is a hard contract: a backend that omits cost keys
(or any analysis call that raises) falls back to the hand FLOP table,
flagged ``source: "analytic"`` — attribution never takes a run down.
"""

from __future__ import annotations

import os

SCHEMA = 1

# ---------------------------------------------------------------- peaks
# Per-device-kind peaks: dense bf16 FLOP/s, HBM bandwidth (bytes/s), HBM
# capacity (bytes). The flops column is bench.py's former PEAK_BF16
# (bench imports it back from here — ONE table, shared). Sources: TPU
# system architecture pages (peak TFLOP/s, HBM GB/s and GiB per chip).
DEVICE_PEAKS: dict[str, dict] = {
    "TPU v4": {"flops": 275e12, "bytes_per_s": 1228e9,
               "capacity_bytes": 32 * 2**30},
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "capacity_bytes": 16 * 2**30},
    "TPU v5e": {"flops": 197e12, "bytes_per_s": 819e9,
                "capacity_bytes": 16 * 2**30},
    "TPU v5": {"flops": 459e12, "bytes_per_s": 2765e9,
               "capacity_bytes": 95 * 2**30},
    "TPU v5p": {"flops": 459e12, "bytes_per_s": 2765e9,
                "capacity_bytes": 95 * 2**30},
    "TPU v6 lite": {"flops": 918e12, "bytes_per_s": 1640e9,
                    "capacity_bytes": 32 * 2**30},
    "TPU v6e": {"flops": 918e12, "bytes_per_s": 1640e9,
                "capacity_bytes": 32 * 2**30},
    # The CPU backend (tests, this container): NOMINAL single-core
    # numbers so roofline/MFU math stays exercised off-chip — flagged
    # nominal, overridable (DTPU_CPU_PEAK_FLOPS / DTPU_CPU_PEAK_BW for
    # a calibrated host), capacity read from /proc/meminfo (the CPU
    # device's memory IS host RAM).
    "cpu": {"flops": 1.0e11, "bytes_per_s": 25.6e9, "capacity_bytes": None,
            "nominal": True},
}

# Hand-maintained fwd FLOPs per 224² image (2 × published GMACs — the
# standard MFU convention, PERF.md "Where the time goes"). This is the
# ANALYTIC FALLBACK and the drift cross-check, no longer the primary
# source; archs with repo-custom dimensions (vit_*, botnet50) are
# deliberately absent — their truth is the XLA analysis alone.
ANALYTIC_FWD_FLOPS_PER_IMG: dict[str, float] = {
    "resnet50": 2 * 4.09e9,        # torchvision count (bench.py's table)
    "resnet18": 2 * 1.82e9,
    "efficientnet_b0": 2 * 0.40e9,
    "regnetx_160": 2 * 15.99e9,    # RegNetX-16GF
    "regnety_160": 2 * 15.96e9,    # RegNetY-16GF
    "regnety_320": 2 * 32.34e9,    # RegNetY-32GF
}

# fwd+bwd+update ≈ 3 × fwd — the bench convention the table shares.
TRAIN_FLOPS_MULT = 3.0

_seen_labels: set[str] = set()
_state: dict = {}


def reset() -> None:
    """Forget which labels were captured (tests; a fresh run per process
    otherwise captures each step program exactly once)."""
    _seen_labels.clear()


def _host_ram_bytes() -> int | None:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def peaks_for(device=None) -> dict | None:
    """Resolved peak entry for one device (default: ``jax.devices()[0]``):
    ``{kind, flops, bytes_per_s, capacity_bytes, capacity_source,
    nominal}`` or None for an unknown device kind. Capacity prefers the
    device's own ``memory_stats()['bytes_limit']`` (the allocator's real
    budget) over the table; the CPU kind falls back to /proc/meminfo."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", str(device))
    entry = DEVICE_PEAKS.get(kind)
    if entry is None:
        return None
    out = {
        "kind": kind,
        "flops": float(entry["flops"]),
        "bytes_per_s": float(entry["bytes_per_s"]),
        "capacity_bytes": entry.get("capacity_bytes"),
        "capacity_source": "table",
        "nominal": bool(entry.get("nominal", False)),
    }
    if kind == "cpu":
        for env, key in (("DTPU_CPU_PEAK_FLOPS", "flops"),
                         ("DTPU_CPU_PEAK_BW", "bytes_per_s")):
            try:
                if os.environ.get(env):
                    out[key] = float(os.environ[env])
                    out["nominal"] = False
            except ValueError:
                pass
        ram = _host_ram_bytes()
        if ram:
            out["capacity_bytes"] = ram
            out["capacity_source"] = "host-ram"
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("bytes_limit"):
        out["capacity_bytes"] = int(stats["bytes_limit"])
        out["capacity_source"] = "memory_stats"
    return out


# ------------------------------------------------------------- analyses
def normalize_cost(analysis) -> dict | None:
    """Normalize a ``cost_analysis()`` result (a dict from Lowered, a
    one-element list of dicts from Compiled) to
    ``{flops, bytes_accessed, transcendentals}``. None when the backend
    omitted the flops key — the caller falls back to the analytic table
    (the graceful-degradation contract)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    flops = analysis.get("flops")
    if flops is None or float(flops) <= 0:
        return None
    return {
        "flops": float(flops),
        "bytes_accessed": float(analysis.get("bytes accessed", 0.0)) or None,
        "transcendentals": float(analysis.get("transcendentals", 0.0)),
    }


def normalize_memory(stats) -> dict | None:
    """Normalize ``Compiled.memory_analysis()`` (CompiledMemoryStats) to
    a byte-count dict. ``total_bytes`` is the peak live footprint model:
    arguments + outputs − aliased (donated buffers are the same memory
    counted on both sides) + temps + generated code. None when the
    backend returns nothing."""
    if stats is None:
        return None
    try:
        arg = int(stats.argument_size_in_bytes)
        out = int(stats.output_size_in_bytes)
        alias = int(stats.alias_size_in_bytes)
        temp = int(stats.temp_size_in_bytes)
        gen = int(stats.generated_code_size_in_bytes)
    except AttributeError:
        return None
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "alias_bytes": alias,
        "temp_bytes": temp,
        "generated_code_bytes": gen,
        "total_bytes": arg + out - alias + temp + gen,
    }


def analytic_step_flops(arch: str, images: int, train: bool = True) -> float | None:
    """Hand-table fallback: per-step flops for ``images`` images, or None
    for an arch outside the table."""
    fwd = ANALYTIC_FWD_FLOPS_PER_IMG.get(arch)
    if fwd is None or images <= 0:
        return None
    return fwd * images * (TRAIN_FLOPS_MULT if train else 1.0)


def drift_pct(measured: float, reference: float) -> float:
    """Signed % by which ``measured`` differs from ``reference`` (the
    bench cross-check: >5 in magnitude means the hand table drifted)."""
    if not reference:
        return 0.0
    return (measured - reference) / abs(reference) * 100.0


def mfu_value(flops_per_step: float, step_seconds: float,
              total_peak_flops: float) -> float | None:
    """Measured model-flops utilization for one step."""
    if not (flops_per_step and step_seconds and total_peak_flops):
        return None
    if step_seconds <= 0 or total_peak_flops <= 0:
        return None
    return flops_per_step / step_seconds / total_peak_flops


def roofline_point(flops: float | None, bytes_accessed: float | None,
                   peaks: dict | None) -> dict | None:
    """Arithmetic intensity vs the device ridge: which roof bounds this
    program. None without flops; intensity alone (no verdict) without a
    peak entry."""
    if not flops:
        return None
    out = {"arithmetic_intensity": None, "ridge_intensity": None,
           "bound": None}
    if bytes_accessed:
        out["arithmetic_intensity"] = flops / bytes_accessed
    if peaks and peaks.get("flops") and peaks.get("bytes_per_s"):
        out["ridge_intensity"] = peaks["flops"] / peaks["bytes_per_s"]
        if out["arithmetic_intensity"] is not None:
            out["bound"] = (
                "compute"
                if out["arithmetic_intensity"] >= out["ridge_intensity"]
                else "memory"
            )
    return out


# ------------------------------------------------------------ the ledger
def build_ledger(
    label: str,
    phase: str,
    cost: dict | None,
    memory: dict | None,
    *,
    images: int,
    arch: str | None = None,
    peaks: dict | None = None,
    n_devices: int = 1,
) -> dict:
    """Assemble the three record payloads (``step`` / ``memory`` /
    ``roofline``) from normalized analyses of one step's program. Pure —
    the shared core of the trainer/serve hooks, bench.py, and
    tools/costmodel_report.py."""
    source = "xla"
    flops = bytes_acc = transc = None
    if cost is not None:
        flops = cost["flops"]
        bytes_acc = cost.get("bytes_accessed") or None
        transc = cost.get("transcendentals", 0.0)
    else:
        source = "analytic"
        flops = analytic_step_flops(
            arch or "", images, train=(phase == "train")
        )
    total_peak = (
        peaks["flops"] * n_devices if peaks and peaks.get("flops") else None
    )
    step_rec = {
        "v": SCHEMA,
        "label": label,
        "phase": phase,
        "flops": round(flops, 1) if flops else None,
        "bytes_accessed": round(bytes_acc, 1) if bytes_acc else None,
        "transcendentals": round(transc, 1) if transc else 0.0,
        "images": int(images),
        "steps_per_call": 1,  # every program is one step (ROADMAP D8)
        "devices": int(n_devices),
        "device_kind": peaks["kind"] if peaks else None,
        "peak_flops": total_peak,
        "source": source,
    }
    roof = roofline_point(flops, bytes_acc, peaks)
    roof_rec = None
    if roof is not None:
        roof_rec = {
            "v": SCHEMA,
            "label": label,
            "phase": phase,
            "arithmetic_intensity": (
                round(roof["arithmetic_intensity"], 3)
                if roof["arithmetic_intensity"] else None
            ),
            "ridge_intensity": (
                round(roof["ridge_intensity"], 3)
                if roof["ridge_intensity"] else None
            ),
            "bound": roof["bound"],
            "peak_flops": peaks["flops"] if peaks else None,
            "peak_bytes_per_s": peaks["bytes_per_s"] if peaks else None,
            "nominal_peaks": bool(peaks.get("nominal")) if peaks else None,
            "source": source,
        }
    mem_rec = None
    if memory is not None:
        capacity = peaks.get("capacity_bytes") if peaks else None
        headroom = None
        if capacity:
            headroom = round((1.0 - memory["total_bytes"] / capacity) * 100, 2)
        mem_rec = {
            "v": SCHEMA,
            "label": label,
            "phase": phase,
            **memory,
            "capacity_bytes": capacity,
            "capacity_source": peaks.get("capacity_source") if peaks else None,
            "headroom_pct": headroom,
            "source": "xla",
        }
    return {"step": step_rec, "memory": mem_rec, "roofline": roof_rec}


def analyze_jitted(fn, args, *, with_memory: bool = False) -> dict:
    """Lower a jitted function against ``args`` (real arrays or
    ShapeDtypeStructs — lowering only traces, nothing executes or is
    donated) and extract the analyses. ``with_memory`` additionally
    AOT-compiles the lowered program for ``memory_analysis()`` — one
    extra backend compile, which runs in an ISOLATED child process while
    the persistent compilation cache is active (``_memory_analysis``).
    Returns ``{"cost", "memory"}`` (each possibly None)."""
    lowered = fn.lower(*args)
    cost = None
    try:
        cost = normalize_cost(lowered.cost_analysis())
    except Exception:
        cost = None
    memory = None
    if with_memory:
        try:
            memory = _memory_analysis(lowered)
        except Exception:
            memory = None
    return {"cost": cost, "memory": memory}


# The isolated AOT probe's child: compiles the parent's lowered
# StableHLO with the SPMD options the module itself declares
# (mhlo.num_partitions / num_replicas) in a pristine process — no
# compilation cache, no restored checkpoint, no trainer threads — and
# prints the executable's memory stats. Byte-identical to the
# in-process ``lowered.compile().memory_analysis()`` (pinned:
# tests/test_asyncplane.py).
_PROBE_CHILD = r"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
ndev = sys.argv[2]
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=" + ndev
).strip()
import jax
from jax._src.lib import xla_client
text = open(sys.argv[1]).read()
m = re.search(r"mhlo.num_partitions = (\d+)", text)
nparts = int(m.group(1)) if m else 1
m = re.search(r"mhlo.num_replicas = (\d+)", text)
nreps = int(m.group(1)) if m else 1
opts = xla_client.CompileOptions()
b = opts.executable_build_options
b.num_partitions = nparts
b.num_replicas = nreps
b.use_spmd_partitioning = nparts > 1
devs = jax.devices()[: nparts * nreps]
exe = devs[0].client.compile_and_load(
    text, xla_client.DeviceList(tuple(devs)), opts
)
ms = exe.get_compiled_memory_stats()
print("PROBE_MEM " + json.dumps({
    "argument_size_in_bytes": ms.argument_size_in_bytes,
    "output_size_in_bytes": ms.output_size_in_bytes,
    "alias_size_in_bytes": ms.alias_size_in_bytes,
    "temp_size_in_bytes": ms.temp_size_in_bytes,
    "generated_code_size_in_bytes": ms.generated_code_size_in_bytes,
}))
"""


class _ProbeStats:
    """Duck-typed CompiledMemoryStats for ``normalize_memory``."""

    def __init__(self, d: dict):
        for k, v in d.items():
            setattr(self, k, int(v))


def probe_memory_subprocess(lowered, timeout: float = 600.0) -> dict | None:
    """The subprocess-isolated AOT probe (ISSUE 11): compile the lowered
    program and read its memory ledger in a CHILD process.

    Why: the in-process ``lowered.compile()`` — one extra AOT compile of
    an already-jitted step program — corrupted the CPU backend's heap
    when the same process ALSO had the persistent compilation cache
    active and a checkpoint restored (PR 10's pinned caveat: segfault /
    malloc-consolidate abort / NaN losses, 6/6 on resume runs). The
    child has none of that state: a fresh backend compiles the exact
    StableHLO the parent lowered (same partition count, SPMD options
    from the module's own attributes) and reports byte-identical memory
    stats, so the cache and the HBM ledger coexist in one run. A dead or
    garbled child degrades to ``None`` (no ledger) — never takes the
    run down."""
    import json
    import subprocess
    import sys
    import tempfile

    import jax

    with tempfile.TemporaryDirectory(prefix="dtpu_memprobe_") as td:
        mod = os.path.join(td, "program.mlir")
        with open(mod, "w") as f:
            f.write(lowered.as_text())
        script = os.path.join(td, "probe.py")
        with open(script, "w") as f:
            f.write(_PROBE_CHILD)
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        proc = subprocess.run(
            [sys.executable, script, mod, str(jax.device_count())],
            capture_output=True, text=True, timeout=timeout, env=env,
        )
    if proc.returncode != 0:
        raise RuntimeError(
            f"memory probe child rc={proc.returncode}: "
            f"{(proc.stdout + proc.stderr)[-500:]}"
        )
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("PROBE_MEM ")][-1]
    return normalize_memory(
        _ProbeStats(json.loads(line[len("PROBE_MEM "):]))
    )


def _memory_analysis(lowered) -> dict | None:
    """The memory half of the ledger for one lowered program: the plain
    in-process AOT compile, except on the CPU backend with the persistent
    compilation cache active, where the compile runs in the
    subprocess-isolated probe (one logged notice) — the heap corruption
    that motivated the probe was pinned on the CPU backend. On the TPU
    the cache is always on (asyncplane/compile_cache.py) and the
    in-process compile is the cached executable's own analysis; PERF.md
    "Bring-up on the chip tool" records the run that checked it."""
    import jax

    if (not jax.config.jax_compilation_cache_dir
            or jax.devices()[0].platform != "cpu"):
        return normalize_memory(lowered.compile().memory_analysis())
    if not _state.get("memory_probe_logged"):
        _state["memory_probe_logged"] = True
        from distribuuuu_tpu.utils.logger import get_logger

        get_logger().info(
            "cost-model HBM ledger: persistent compilation cache active "
            "— running the COSTMODEL_MEMORY AOT compile in an isolated "
            "child process (telemetry/costmodel.py subprocess probe)"
        )
    return probe_memory_subprocess(lowered)


def analyze_compiled(compiled) -> dict:
    """Analyses from an executable the caller already compiled (the
    serving engine's AOT buckets) — no extra compile at all."""
    cost = memory = None
    try:
        cost = normalize_cost(compiled.cost_analysis())
    except Exception:
        cost = None
    try:
        memory = normalize_memory(compiled.memory_analysis())
    except Exception:
        memory = None
    return {"cost": cost, "memory": memory}


# Resolved mesh/topology of the live run (set by the trainer once the
# partition topology is classified): attached to every cost.step record
# so post-mortem consumers attribute the ledger per mesh axis without
# re-deriving the stanza. Free-form extras per the schema contract.
_mesh_extras: dict | None = None


def set_mesh_extras(extras: dict | None) -> None:
    """Attach ``{"mesh": {axis: size}, "topology": class_name}`` to
    subsequent cost.step records (None clears — tests)."""
    global _mesh_extras
    _mesh_extras = dict(extras) if extras else None


def _emit(ledger: dict) -> None:
    from distribuuuu_tpu.telemetry import spans

    step = dict(ledger["step"])
    if _mesh_extras:
        step.update(_mesh_extras)
    spans.emit_event("cost.step", **step)
    if ledger["memory"] is not None:
        spans.emit_event("cost.memory", **ledger["memory"])
    if ledger["roofline"] is not None:
        spans.emit_event("cost.roofline", **ledger["roofline"])


def capture_step(
    fn,
    args,
    *,
    label: str,
    phase: str,
    images: int,
    arch: str | None = None,
    with_memory: bool = False,
) -> dict | None:
    """The trainer hook: once per ``label`` per process, lower ``fn``
    against the live ``(state, batch)`` (pre-donation — lowering reads
    shapes, not buffers), emit the cost/memory/roofline records into the
    per-rank sink, and return the ledger. Never raises and no-ops while
    telemetry is off — attribution must not take a run down."""
    from distribuuuu_tpu.telemetry import spans

    if not spans.enabled() or label in _seen_labels:
        return None
    _seen_labels.add(label)
    try:
        analyses = analyze_jitted(fn, args, with_memory=with_memory)
        import jax

        ledger = build_ledger(
            label, phase, analyses["cost"], analyses["memory"],
            images=images, arch=arch,
            peaks=peaks_for(), n_devices=len(jax.devices()),
        )
        _emit(ledger)
        return ledger
    except Exception as e:  # noqa: BLE001 — observability never fatal
        try:
            from distribuuuu_tpu.utils.logger import get_logger

            get_logger().warning("cost capture for %r failed: %s", label, e)
        except Exception:
            pass
        return None


def capture_compiled(
    compiled,
    *,
    label: str,
    phase: str,
    images: int,
    arch: str | None = None,
) -> dict | None:
    """The serving hook: same emission as ``capture_step`` but reading an
    already-compiled executable (the engine's AOT buckets) — zero extra
    compiles."""
    from distribuuuu_tpu.telemetry import spans

    if not spans.enabled() or label in _seen_labels:
        return None
    _seen_labels.add(label)
    try:
        analyses = analyze_compiled(compiled)
        import jax

        ledger = build_ledger(
            label, phase, analyses["cost"], analyses["memory"],
            images=images, arch=arch, peaks=peaks_for(),
            n_devices=len(jax.devices()),
        )
        _emit(ledger)
        return ledger
    except Exception:  # noqa: BLE001 — observability never fatal
        return None
