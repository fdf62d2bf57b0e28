"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one 256-chip pod; (2,16,16) = two pods (512 chips).

    Axes: `model` is the paper's spatial Lego-tiling axis (TP/EP);
    `data` is FSDP/DP; `pod` is pure DP across pods (multi-pod only).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A mesh whose axes the compiler partitions over (`AxisType.Auto`):
    the model code states shardings with `NamedSharding` and leaves the
    rest to GSPMD, which jax's default of explicit axes would refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))
