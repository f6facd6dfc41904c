"""Seeds derived from the run's ``--seed``: every input of a run is a
function of the seed and of where it is used."""
from __future__ import annotations

import numpy as np


def derive(seed: int, *tags: int) -> int:
    """A 31-bit seed for the part named by ``tags``."""
    ss = np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFF, *map(int, tags)])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFF, *map(int, tags)]))
