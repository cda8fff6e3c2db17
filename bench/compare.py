"""What the correctness checks share: the error measure and the seeded
choice of which answers to compare."""

from __future__ import annotations

import numpy as np


def rel_err(got, want) -> float:
    """The widest gap, as a share of the reference's largest magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} against the reference's {want.shape}")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    if not np.isfinite(err):
        return float("inf")
    return err / scale if scale > 0 else err


class Sample:
    """Keeps one item of a stream, each with equal chance, drawn from the
    seed (reservoir sampling), and the stream's last item besides."""

    def __init__(self, seed: int):
        self._rng = np.random.default_rng(seed)
        self.seen = 0
        self.drawn = None
        self.last = None

    def offer(self, item) -> None:
        self.seen += 1
        if self._rng.integers(self.seen) == 0:
            self.drawn = item
        self.last = item

    def items(self) -> list:
        """The drawn item and the last one (once, where they are the same)."""
        if self.last is None:
            return []
        return [self.last] if self.drawn is self.last else [self.drawn, self.last]
