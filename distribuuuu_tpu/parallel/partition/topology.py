"""The topology registry: validate / classify / enumerate mesh stanzas.

Before this layer every invalid ``MESH`` stanza died in a different place
— ``check_trainer_mesh`` refusals, a model constructor assert, a GSPMD
shape error three layers down — and whole valid regions of the mesh
space (ZeRO-3 under PP; a dp×tp×ep 3-axis mesh) had no code path because
no refusal had been *removed* for them. Here the mesh space is a first-
class object:

  * :func:`from_cfg` resolves a stanza (wildcards included) into a
    :class:`Topology` and validates it against a CAPABILITY table — one
    rule per (feature, arch-family) pair, each carrying the actionable
    error. A stanza that passes is guaranteed a code path through the
    partition lowering.
  * :func:`enumerate_topologies` walks every factorization of the device
    count over the mesh axes × ZeRO stages and yields the valid ones —
    the generator behind ``tools/mesh_sweep.py`` (the MULTICHIP dryrun
    matrix is generated, not hand-enumerated).
  * :meth:`Topology.describe` is the layout record checkpoint manifests
    embed, so elastic resume classifies partition-layer layouts
    (resilience/manifest.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from distribuuuu_tpu.parallel import mesh as mesh_lib


class TopologyError(ValueError):
    """A MESH stanza the capability table refuses (with the reason)."""


# depth of the shipped ViT archs — lets the registry refuse an indivisible
# pipe size at stanza validation instead of deep inside model.init
_VIT_DEPTH = {"vit_tiny": 12, "vit_small": 12, "vit_tiny_moe": 12}

_FEATURE_ORDER = ("dp", "tp", "sp", "pp", "ep", "zero1", "zero3")


@dataclass(frozen=True)
class Topology:
    """One resolved point of the mesh space: axis sizes + ZeRO stage
    (+ the GPipe microbatch count when a pipe axis is present)."""

    data: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    zero: int = 0
    microbatch: int = 0  # 0 → 2 × pipe (parallel/pp.py default)

    @property
    def axes(self) -> dict[str, int]:
        return {
            "data": self.data, "model": self.model, "seq": self.seq,
            "pipe": self.pipe, "expert": self.expert,
        }

    def devices(self) -> int:
        n = 1
        for v in self.axes.values():
            n *= v
        return n

    def features(self) -> frozenset[str]:
        feats = set()
        if self.data > 1:
            feats.add("dp")
        if self.model > 1:
            feats.add("tp")
        if self.seq > 1:
            feats.add("sp")
        if self.pipe > 1:
            feats.add("pp")
        if self.expert > 1:
            feats.add("ep")
        if self.zero == 1:
            feats.add("zero1")
        elif self.zero == 3:
            feats.add("zero3")
        return frozenset(feats)

    def class_name(self) -> str:
        """Stable human name, e.g. ``dp2·tp2·ep2·zero1`` (``dp1`` for the
        single-chip degenerate point)."""
        parts = []
        for feat, size in (
            ("dp", self.data), ("tp", self.model), ("sp", self.seq),
            ("pp", self.pipe), ("ep", self.expert),
        ):
            if size > 1:
                parts.append(f"{feat}{size}")
        if self.zero:
            parts.append(f"zero{self.zero}")
        return "·".join(parts) or "dp1"

    def mesh_stanza(self) -> dict:
        """The YAML ``MESH`` stanza reproducing this topology (the sweep
        writes these verbatim; merge with ``cfg.MESH``)."""
        out = {
            "DATA": self.data, "MODEL": self.model, "SEQ": self.seq,
            "PIPE": self.pipe, "EXPERT": self.expert, "ZERO": self.zero,
        }
        if self.pipe > 1:
            out["MICROBATCH"] = self.microbatch or 2 * self.pipe
        return out

    def describe(self) -> dict:
        """The layout record manifests embed (resilience/manifest.py):
        resolved axes, stage, feature set, class name."""
        return {
            "axes": self.axes,
            "zero": self.zero,
            "features": sorted(
                self.features(), key=_FEATURE_ORDER.index
            ),
            "class": self.class_name(),
        }

    def build_mesh(self, devices=None):
        return mesh_lib.build_mesh(
            data=self.data, model=self.model, seq=self.seq, pipe=self.pipe,
            expert=self.expert, devices=devices,
        )

    def moe_axis(self) -> str:
        """Mesh axis MoE expert tensors/dispatch ride: the dedicated
        ``expert`` axis when populated, else the legacy ``model`` axis."""
        return "expert" if self.expert > 1 else "model"


# ------------------------------------------------------- capability rules


@dataclass(frozen=True)
class Rule:
    """One capability-derived refusal: ``broken(topo, arch, moe)``
    returning an error string (or None when the stanza is fine)."""

    name: str
    broken: Callable

    def check(self, topo: Topology, arch: str, moe) -> str | None:
        return self.broken(topo, arch, moe)


def _is_vit(arch: str) -> bool:
    return arch.startswith("vit")


def _is_gpt(arch: str) -> bool:
    return arch.startswith("gpt")


def _is_moe(arch: str) -> bool:
    return arch.endswith("_moe")


def _rule_zero_stage(t, arch, moe):
    if t.zero not in (0, 1, 3):
        return (
            f"MESH.ZERO={t.zero}: stages are 0 (off), 1 (optimizer state "
            "sharded over data), 3 (params too — FSDP); stage 2 is "
            "subsumed by 1 in a fused jit step (parallel/zero.py)"
        )
    return None


def _rule_pipe_arch(t, arch, moe):
    if t.pipe > 1 and not _is_vit(arch):
        return (
            f"MESH.PIPE={t.pipe}: only the ViT archs satisfy the "
            "uniform-stage pipeline contract (parallel/pp.py); a CNN's "
            "shrinking stage pyramid does not — use MESH.DATA/MODEL "
            "for those archs"
        )
    return None


def _rule_pipe_depth(t, arch, moe):
    depth = _VIT_DEPTH.get(arch)
    if t.pipe > 1 and depth is not None and depth % t.pipe:
        return (
            f"MESH.PIPE={t.pipe}: depth {depth} of {arch!r} not divisible "
            "by pipe_stages (models/vit.PipelinedViT uniform-stage "
            "contract)"
        )
    return None


def _rule_pipe_moe_every(t, arch, moe):
    depth = _VIT_DEPTH.get(arch)
    if (
        t.pipe > 1 and _is_moe(arch) and depth is not None and moe is not None
        and (depth // t.pipe) % int(moe.EVERY)
    ):
        return (
            f"MESH.PIPE={t.pipe} with {arch!r}: PP×MoE needs "
            f"blocks-per-stage ({depth // t.pipe}) divisible by "
            f"MODEL.MOE.EVERY ({int(moe.EVERY)}); adjust MESH.PIPE or "
            "MODEL.MOE.EVERY"
        )
    return None


def _rule_pipe_seq(t, arch, moe):
    if t.pipe > 1 and t.seq > 1:
        return (
            f"MESH.PIPE={t.pipe} with MESH.SEQ={t.seq}: sequence-SHARDED "
            "(ring/ulysses) attention does not compose with the pipe axis "
            "— PP shards depth, SP shards tokens; per-device "
            "flash/blockwise attention inside stages is supported instead "
            "(DEVICE.ATTN_IMPL flash)"
        )
    return None


def _rule_seq_arch(t, arch, moe):
    if t.seq > 1 and not (_is_vit(arch) or _is_gpt(arch)):
        return (
            f"MESH.SEQ={t.seq}: only the ViT and GPT archs route "
            "attention over the seq axis (ring/ulysses, "
            "ops/ring_attention.py); CNN archs have no sequence dimension "
            "to shard (the axis would be silently replicated)"
        )
    return None


def _rule_expert_arch(t, arch, moe):
    if t.expert > 1 and not _is_moe(arch):
        return (
            f"MESH.EXPERT={t.expert}: only the *_moe archs dispatch "
            "experts; a dense arch would silently replicate the whole "
            "computation over the expert axis — use MESH.DATA/MODEL "
            "for those archs"
        )
    return None


def _rule_expert_divides(t, arch, moe):
    if t.expert > 1 and moe is not None and int(moe.NUM_EXPERTS) % t.expert:
        return (
            f"MESH.EXPERT={t.expert} must divide MODEL.MOE.NUM_EXPERTS="
            f"{int(moe.NUM_EXPERTS)} (each expert-axis rank owns an equal "
            "slice of the expert tensors)"
        )
    return None


def _rule_expert_seq(t, arch, moe):
    if t.expert > 1 and t.seq > 1:
        return (
            f"MESH.EXPERT={t.expert} with MESH.SEQ={t.seq}: sequence-"
            "sharded attention and dedicated-axis expert dispatch both "
            "want the token dim — compose EP with data/model/pipe axes "
            "instead"
        )
    return None


def _rule_declared_axes(t, arch, moe):
    from distribuuuu_tpu import models

    axes = models.traits(arch).mesh_axes
    if axes is None:
        return None
    sizes = {"data": t.data, "model": t.model, "seq": t.seq, "pipe": t.pipe,
             "expert": t.expert}
    extra = {name: n for name, n in sizes.items() if n > 1 and name not in axes}
    if extra:
        return (
            f"{arch!r} declares (models/traits.py) that it lowers on "
            + "/".join(f"MESH.{a.upper()}=n" for a in axes)
            + " meshes only, got "
            + " ".join(f"{name}={n}" for name, n in extra.items())
        )
    return None


# NOTE what is deliberately ABSENT here: the old trainer refusal of
# MESH.ZERO=3 with MESH.PIPE>1. Under the partition layer FSDP params are
# a rest LAYOUT — GSPMD derives the gather at the stage shard_map
# boundary from the in_specs and autodiff transposes it to the
# reduce-scatter — so ZeRO-3 × PP is a supported composition, exercised
# by the dryrun sweep and tests/test_partition_lowering.py.
RULES: tuple[Rule, ...] = (
    Rule("zero_stage", _rule_zero_stage),
    Rule("declared_axes", _rule_declared_axes),
    Rule("pipe_arch", _rule_pipe_arch),
    Rule("pipe_depth", _rule_pipe_depth),
    Rule("pipe_moe_every", _rule_pipe_moe_every),
    Rule("pipe_seq", _rule_pipe_seq),
    Rule("seq_arch", _rule_seq_arch),
    Rule("expert_arch", _rule_expert_arch),
    Rule("expert_divides", _rule_expert_divides),
    Rule("expert_seq", _rule_expert_seq),
)


def validate(topo: Topology, arch: str, moe=None) -> Topology:
    """Run the capability table; raises :class:`TopologyError` with the
    first broken rule's actionable message, returns ``topo`` unchanged
    otherwise."""
    for rule in RULES:
        msg = rule.check(topo, arch, moe)
        if msg is not None:
            raise TopologyError(msg)
    return topo


def from_cfg(cfg, n_devices: int | None = None) -> Topology:
    """Resolve + validate the live config's MESH stanza.

    ``n_devices`` defaults to ``jax.device_count()`` (wildcard resolution
    needs it). Raises :class:`TopologyError` for stanzas the capability
    table refuses and ``ValueError`` for shapes that don't divide the
    device count — both BEFORE any expensive init/compile.
    """
    if n_devices is None:
        import jax

        n_devices = jax.device_count()
    raw = [
        cfg.MESH.DATA, cfg.MESH.MODEL, cfg.MESH.SEQ, cfg.MESH.PIPE,
        cfg.MESH.get("EXPERT", 1),
    ]
    sizes = mesh_lib.resolve_axis_sizes(raw, n_devices)
    topo = Topology(
        data=sizes[0], model=sizes[1], seq=sizes[2], pipe=sizes[3],
        expert=sizes[4], zero=int(cfg.MESH.ZERO),
        microbatch=int(cfg.MESH.MICROBATCH),
    )
    return validate(topo, cfg.MODEL.ARCH, cfg.MODEL.MOE)


# ------------------------------------------------------------ enumeration


def _factorizations(n: int, k: int):
    """All ordered k-tuples of positive ints with product n."""
    if k == 1:
        yield (n,)
        return
    for d in range(1, n + 1):
        if n % d == 0:
            for rest in _factorizations(n // d, k - 1):
                yield (d,) + rest


def default_arch_for(topo: Topology) -> str:
    """Representative zoo arch for a topology's feature set: MoE archs
    where an expert population needs dispatch, ViT where pipe/seq axes
    need the uniform-stage/attention contract, the CNN flagship
    otherwise."""
    feats = topo.features()
    if "ep" in feats:
        return "vit_tiny_moe"
    if "pp" in feats or "sp" in feats:
        return "vit_tiny"
    return "resnet18"


def enumerate_topologies(
    n_devices: int, zero_stages=(0, 1, 3), max_axes: int = 3,
):
    """Yield every VALID ``(topology, arch)`` over the device count:
    all factorizations of ``n_devices`` into the mesh axes (at most
    ``max_axes`` non-unit axes — 4-axis meshes on 8 devices degenerate
    to 2-way everything and add no coverage class) × ZeRO stages, each
    validated against its representative arch through the SAME rule
    table ``from_cfg`` runs. Deterministic order (sorted by class name).
    """
    from distribuuuu_tpu.config import cfg as _cfg

    seen = set()
    out = []
    for sizes in _factorizations(n_devices, 5):
        if sum(1 for s in sizes if s > 1) > max_axes:
            continue
        for zero in zero_stages:
            topo = Topology(
                data=sizes[0], model=sizes[1], seq=sizes[2],
                pipe=sizes[3], expert=sizes[4], zero=zero,
            )
            arch = default_arch_for(topo)
            try:
                validate(topo, arch, _cfg.MODEL.MOE)
            except TopologyError:
                continue
            key = (sizes, zero)
            if key in seen:
                continue
            seen.add(key)
            out.append((topo, arch))
    out.sort(key=lambda ta: (ta[0].class_name(), ta[0].axes["data"]))
    return out


def classify_transition(saved: dict | None, live: dict | None) -> tuple[str, str]:
    """Elastic-resume compatibility of two :meth:`Topology.describe`
    records: ``("exact"|"reshardable", detail)``.

    Partition-layer layouts are reshardable across EVERY axis/stage
    change — arrays re-place onto the live layout leaf by leaf
    (trainer._place_like; ZeRO shards reassemble through canonical leaf
    order) — so the classification's job is the DETAIL: which axes and
    stage moved, for the operator log and the resume drills. Model
    incompatibility is decided by the manifest's tree/fingerprint check,
    not here."""
    saved, live = saved or {}, live or {}
    s_axes, l_axes = saved.get("axes") or {}, live.get("axes") or {}
    diffs = [
        f"{ax} {s_axes.get(ax, 1)}→{l_axes.get(ax, 1)}"
        for ax in sorted(set(s_axes) | set(l_axes))
        if int(s_axes.get(ax, 1)) != int(l_axes.get(ax, 1))
    ]
    if saved.get("zero", 0) != live.get("zero", 0):
        diffs.append(f"zero {saved.get('zero', 0)}→{live.get('zero', 0)}")
    if not diffs:
        return "exact", ""
    return "reshardable", (
        f"partition layout {saved.get('class', '?')}→"
        f"{live.get('class', '?')} ({'; '.join(diffs)})"
    )
