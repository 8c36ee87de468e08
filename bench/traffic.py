"""The one traffic generator: a mix's parameters and a seed become sweeps.

A mix (``bench/traffic/<name>.json``) lists combined error constraints,
each at its own ``gauss_sigma`` or the mix's.  A sweep runs a list of
constraints, each from the same block of consecutive CGP seeds, as many as
the program's chunk holds, so every chunk is one constraint and no chunk
is padded.  The order of the constraints is a permutation drawn from the
run's seed, and so is the first CGP seed of each sweep; a window that needs
more chunks than the mix has constraints runs further sweeps with fresh
seed blocks.

A mix may also set keys of its cells' configuration (``"config"``, e.g. a
mutation rate) and the program's sweep settings (``"sweep"``: keyword
arguments of ``SweepConfig``, e.g. ``{"dedup": true}``).
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")
#: CGP seeds stay in int32, the range ``PRNGKey`` takes without x64
SEED_SPACE = 1 << 30


@dataclass(frozen=True)
class Sweep:
    """One whole sweep: every constraint runs from every seed."""
    constraints: tuple[dict, ...]
    seeds: tuple[int, ...]

    @property
    def runs(self) -> int:
        return len(self.constraints) * len(self.seeds)

    def row(self, r: int) -> tuple[dict, int]:
        """Constraint and CGP seed of grid row ``r`` (constraints outer)."""
        return self.constraints[r // len(self.seeds)], \
            self.seeds[r % len(self.seeds)]


def load(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        return json.load(f)


def constraints(mix: dict) -> list[dict]:
    """The mix's constraints, each with its Gauss sigma stated."""
    return [{"gauss_sigma": float(mix["gauss_sigma"]), **c}
            for c in mix["constraints"]]


def _draw(seed: int, stream: str) -> np.random.Generator:
    digest = hashlib.sha256(f"{seed}/{stream}".encode()).digest()
    return np.random.default_rng(int.from_bytes(digest[:8], "little"))


def sweeps(mix: dict, seed: int, n_chunks: int, chunk_size: int
           ) -> list[Sweep]:
    """``n_chunks`` whole chunks of ``chunk_size`` runs, as whole sweeps."""
    rng = _draw(seed, "window")
    cons = constraints(mix)
    out = []
    while n_chunks > 0:
        take = min(n_chunks, len(cons))
        order = rng.permutation(len(cons))[:take]
        base = int(rng.integers(0, SEED_SPACE - chunk_size))
        out.append(Sweep(tuple(cons[i] for i in order),
                         tuple(range(base, base + chunk_size))))
        n_chunks -= take
    return out


def warmup(mix: dict, seed: int, chunk_size: int) -> Sweep:
    """One chunk of each Gauss sigma of the mix (each sigma is a program of
    its own), drawn from a stream of the seed that the window does not
    use."""
    rng = _draw(seed, "warmup")
    cons = constraints(mix)
    first: dict[float, dict] = {}
    for i in rng.permutation(len(cons)):
        first.setdefault(cons[i]["gauss_sigma"], cons[i])
    base = int(rng.integers(0, SEED_SPACE - chunk_size))
    return Sweep(tuple(first.values()), tuple(range(base, base + chunk_size)))


def sample(seed: int, stream: str, population: int, k: int) -> np.ndarray:
    """``k`` distinct indices of ``population``, drawn from the seed."""
    return np.sort(_draw(seed, stream).choice(population, min(k, population),
                                              replace=False))
