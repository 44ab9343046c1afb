"""Training window of ``one_pass``, with a check spread over the whole bank.

Set-up, the window and the reference are ``one_pass``'s. The check compares
every ``check_stride``-th model, from an offset in ``[0, check_stride)``
drawn from the seed: every run of ``check_stride`` consecutive model ids
holds one checked model, so every bank tile of that many models or more,
the ragged last tile too, is checked in every run.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.drivers import one_pass


class Cell(one_pass.Cell):
    def models(self) -> np.ndarray:
        stride = self.ctx.traffic["check_stride"]
        start = int(self.ctx.rng(2).integers(min(stride, self.b)))
        return np.arange(start, self.b, stride)
