"""Production mesh factory.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
pure data parallelism over the slower inter-pod tier.

A FUNCTION, not a module constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Every mesh here uses ``Auto`` axis types: the model code places
activations with sharding constraints and lets the partitioner
propagate the rest (``jax.make_mesh`` defaults to ``Explicit`` axes,
under which reshapes and gathers of a sharded batch need an explicit
output sharding).
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "mesh_num_chips",
           "ici_links"]


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with ``Auto`` axis types (see the module note)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_num_chips(mesh) -> int:
    import numpy as np
    return int(np.prod(mesh.devices.shape))


def ici_links(mesh=None, spec=None) -> int:
    """Links per chip for the collective roofline term, derived from the
    target spec's ICI topology (v5e/v6e 2D torus -> 4, v4/v5p 3D torus
    -> 6).  ``spec=None`` uses the process-default target; ``mesh`` is
    accepted for call-site symmetry with `mesh_num_chips` but the link
    count is a chip property, not a mesh property."""
    from repro.core.hw import require_tpu
    return require_tpu(spec, "launch.mesh.ici_links").ici_links
