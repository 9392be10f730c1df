"""Block shapes for the parametric tile kernel.

One frozen record carries everything the autotuner can move:

  * ``r``                 -- tiles per task (row-block of the mix GEMMs;
                             the paper's R, bounded by shared-memory
                             capacity via ``analysis.max_r_ta``)
  * ``tasks_per_program`` -- tasks fused into one Pallas program
                             (grid-size vs working-set trade).  On the
                             XLA matrix path the product
                             ``r * tasks_per_program`` becomes the tile
                             chunk of one sweep; the sentinel 0 means
                             "unchunked" -- the whole tile population in
                             one GEMM chain, which is what wins on large
                             cache-friendly CPUs.

Serialized as a plain dict under the ``"blocks"`` field of a wisdom
entry so it rides the existing ``backend:family:geometry`` keys and
survives ``tune.py`` atomic rewrites unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    r: int
    tasks_per_program: int = 0

    def chunk(self) -> int:
        """Tiles per sweep on the matrix path (0 = whole population)."""
        if self.tasks_per_program <= 0:
            return 0
        return self.r * self.tasks_per_program

    def to_wisdom(self) -> dict:
        return {
            "r": int(self.r),
            "tpp": int(self.tasks_per_program),
        }

    @classmethod
    def from_wisdom(cls, d: Mapping) -> Optional["BlockConfig"]:
        try:
            return cls(
                r=int(d["r"]),
                tasks_per_program=int(d.get("tpp", 0)),
            )
        except (KeyError, TypeError, ValueError):
            return None
