"""Benchmark utilities: timing + CSV emission.

CPU wall-clock numbers are DIRECTIONAL ONLY (the paper measured V100s;
this container is one CPU core) — every table also emits the structural
metric that transfers to TPU (bytes moved / FLOPs / layout effect ratios),
derived from the loop-aware HLO analysis where relevant.

The timing harness itself lives in ``repro.tuning.timing`` — the SAME
split-timing implementation the measured autotuner uses, re-exported
here so every table and the tuner report comparable numbers.
"""

from __future__ import annotations

from repro.tuning.timing import time_fn, time_fn_split  # noqa: F401

__all__ = ["time_fn", "time_fn_split", "Csv", "gbps"]


def gbps(nbytes: float, ms: float) -> float:
    """Achieved bandwidth in GB/s from known bytes-moved and measured
    milliseconds — the bandwidth every table row reports next to its
    wall-clock."""
    return nbytes / max(ms * 1e-3, 1e-12) / 1e9


class Csv:
    def __init__(self, *cols: str):
        self.cols = cols
        self.rows: list[tuple] = []
        print(",".join(cols), flush=True)

    def row(self, *vals) -> None:
        vals = tuple(f"{v:.4f}" if isinstance(v, float) else str(v)
                     for v in vals)
        self.rows.append(vals)
        print(",".join(vals), flush=True)

    def dicts(self) -> list[dict]:
        """Rows as JSON-ready records (``benchmarks.run --json``)."""
        return [dict(zip(self.cols, r)) for r in self.rows]
