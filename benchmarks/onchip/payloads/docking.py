"""Docking: a CPU-bound scoring function per molecule (the AutoDock
analogue of the IMPECCABLE campaign), run as a Python function task."""
from __future__ import annotations

import numpy as np


def docking(mol: np.ndarray) -> float:
    return float(np.sum(np.sin(mol) ** 2))


class Payload:
    name = "docking"
    flops = 0.0

    def __init__(self, run, spec):
        self.run = run
        self.width = int(spec["width"])
        self.fn = docking

    def setup(self):
        pass

    def free(self):
        pass
