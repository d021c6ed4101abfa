"""The one generator of every traffic mix: it reads a mix's parameters
from ``bench/traffic/<mix>.json`` and derives every random choice from
``--seed``, so the same seed gives the same inputs."""
from __future__ import annotations

import numpy as np


def stream(seed: int, *path: int) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


def job_seed(seed: int, job: int) -> int:
    """The 32-bit key seed of one sampling job."""
    return int(np.random.SeedSequence([int(seed), 1, job])
               .generate_state(1, np.uint32)[0])
