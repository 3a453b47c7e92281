"""What one serve dispatch needs, and the least time it could take.

The yardstick for ``step_roofline`` and ``step.mfu``.  Each
architecture module counts a dispatch's work from shapes
(``dispatch_work``, ``bench/arch/``); this module holds what every
architecture shares: the record of that work, and the roofline over a
device's peaks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass
class Work:
    flops: float = 0.0              # a multiply-add counts two
    bytes: float = 0.0              # K/V read and written
    weight_bytes: float = 0.0       # weights read

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes,
                    self.weight_bytes + o.weight_bytes)


def least_seconds(w: Work, peak: Dict) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(w.flops / peak["bf16_flops"],
               (w.bytes + w.weight_bytes) / peak["hbm_bytes_s"])
