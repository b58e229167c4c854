"""repro.dist — the runtime layer of the cross-layer design.

The paper's compiler layer (:mod:`repro.core.hints` / ``wfcompiler``) decides
*what* should move; this package is the runtime that binds those decisions to
device placement:

  sharding     divisibility-aware PartitionSpec rules for params / batches /
               decode caches on the production meshes
  hints        ``sharding_rules(mesh)`` context + ``hint(x, *roles)`` — the
               lazy in-model annotation hook every layer calls
  compression  int8 error-feedback gradient compression for DP collectives
  mesh         ``Auto``-axis (data, model) meshes over the local devices
"""

from repro.dist import compression, hints, mesh, sharding

__all__ = ["compression", "hints", "mesh", "sharding"]
