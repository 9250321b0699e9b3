"""The numbers a run compares with its reference, each against its limit."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Readings:
    values: dict
    limits: dict

    def compared(self) -> list[str]:
        return [k for k in self.values if k in self.limits]

    def lines(self) -> list[str]:
        return [f"{k} {self.values[k]!r} limit {self.limits[k]!r}"
                for k in self.compared()]

    @property
    def correct(self) -> bool:
        return all(math.isfinite(self.values[k])
                   and self.values[k] <= self.limits[k]
                   for k in self.compared())
