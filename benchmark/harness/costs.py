"""What the algorithm has to move, computed from shapes alone: the same
number whatever implements the step."""

from __future__ import annotations

import math


def tree_bytes(shapes) -> int:
    """Bytes of a tree given as ``(shape, itemsize)`` pairs, one per leaf."""
    return sum(math.prod(shape) * itemsize for shape, itemsize in shapes)


def phase_floor_seconds(state_bytes: int, hbm_bytes_per_s: float) -> float:
    """The least time one phase step can take on a chip: the carried state
    read once. The step is integer word algebra with no matrix product,
    so bytes bound it, and reading the state once (never mind writing it
    back) is a floor no implementation can go under."""
    return state_bytes / hbm_bytes_per_s
