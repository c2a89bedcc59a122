"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The production target is a TPU v5e pod slice:
16x16 = 256 chips per pod ("data" x "model"), and 2 pods = 512 chips for the
multi-pod configuration with a leading "pod" axis (outer data parallelism /
FSDP axis; gradients reduce over ("pod", "data")).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    # Auto axes: the sharding rules place arrays with with_sharding_constraint
    # under an ambient mesh, which explicit-axis meshes (the default of
    # jax.make_mesh) reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / reduced-config runs)."""
    n = len(jax.devices())
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))
