"""Distribution substrate for the MPDCompress reproduction.

Submodules:

- :mod:`repro.dist.sharding`  — logical-axis sharding: rule tables mapping
  logical names (``"batch"``, ``"heads"``, ``"blocks"``, ...) to mesh axes,
  the :func:`~repro.dist.sharding.shard` activation constraint, and
  pytree-level ``NamedSharding`` derivation for params/optimizer/caches.
- :mod:`repro.dist.mesh`      — mesh constructors (production pod shapes and
  the forced-host-device test mesh).
- :mod:`repro.dist.compress`  — int-k gradient quantization with error
  feedback (wire-size reduction for the DP all-reduce).
- :mod:`repro.dist.microbatch` — divisibility-aware gradient-accumulation
  microbatching shared by the train loop and the dry-run cell programs.
- :mod:`repro.dist.straggler` — step-time outlier detection with
  checkpoint-escalation verdicts.
- :mod:`repro.dist.pipeline`  — GPipe-style pipeline parallel forward over a
  mesh axis (ppermute rotation schedule).

The package is import-safe on a single CPU device: nothing here touches jax
device state at import time, and every entry point degrades to an identity /
local implementation when no mesh is active.
"""

from . import compress, mesh, microbatch, pipeline, sharding, straggler  # noqa: F401
from .mesh import data_axes, make_host_mesh, make_mesh, make_production_mesh  # noqa: F401
from .sharding import (  # noqa: F401
    block_parallel_rules,
    current,
    current_mesh,
    current_rules,
    default_rules,
    long_context_rules,
    shard,
    spec_for,
    tp_rules,
    tree_shardings,
    use_mesh,
    use_mesh_rules,
)
from .straggler import StragglerMonitor  # noqa: F401
