"""Unified partition layer: declarative per-leaf PartitionSpecs and ONE
lowering for arbitrary dp×tp×pp×ep×sp meshes (ROADMAP #3; grounding:
"Scalable Training of Language Models using JAX pjit and TPUv4",
arXiv:2204.06514 — every parallelism form expressed as per-leaf specs
over one mesh, one lowering; the ZeRO composition that falls out for
free is arXiv:2004.13336).

Three layers:

  specs.py     the spec layer — per-leaf PartitionSpec declaration
               (model annotations + a path-pattern rules table covering
               the zoo), spec algebra (validate / collapse-at-size-1 /
               canonicalize), and the TP/ZeRO/PP layouts expressed as
               spec TRANSFORMS over declared base specs
  topology.py  the topology registry — validates/classifies any MESH
               stanza up front (capability-derived errors replacing the
               scattered trainer refusals), enumerates the valid mesh
               space for the dryrun sweep, and feeds elastic-resume
               classification (resilience/manifest.py)
  lowering.py  the one pjit-style lowering — builds the train/eval
               step from specs alone for ANY validated topology
               (the trainer's accum/ZeRO/PP/EP case analysis
               collapsed into a single code path)

Compositions that previously had no code path — ZeRO-3 under PP, and a
3-axis dp×tp×ep mesh with ZeRO-1 — train through this layer from a YAML
mesh stanza alone; every pre-existing topology reproduces its trajectory
(lockstep-tolerance-pinned in tests/test_partition_lowering.py).
"""

from distribuuuu_tpu.parallel.partition.specs import (  # noqa: F401
    SpecTable,
    SpecRule,
    UnknownLeafError,
    SpecConflictError,
    batch_spec,
    canonicalize,
    collapse_unit_axes,
    state_layout,
    validate_leaf_spec,
)
from distribuuuu_tpu.parallel.partition.topology import (  # noqa: F401
    Topology,
    TopologyError,
    enumerate_topologies,
    from_cfg,
)
from distribuuuu_tpu.parallel.partition.lowering import (  # noqa: F401
    Lowered,
    lower,
    make_eval_step,
    make_train_step,
)
