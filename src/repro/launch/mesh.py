"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests, which must see
one CPU device, while ``dryrun.py`` forces 512 placeholder host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(devices, axes):
    return jax.sharding.Mesh(devices, axes,
                             axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; "
            "run under launch/dryrun.py (XLA_FLAGS host device count)")
    import numpy as np
    return _mesh(np.asarray(devices).reshape(shape), axes)


def make_local_mesh(shape=(1, 1), axes=("data", "model")):
    """Smoke-test mesh over however many local devices exist."""
    import numpy as np
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    return _mesh(np.asarray(devices).reshape(shape), axes)
