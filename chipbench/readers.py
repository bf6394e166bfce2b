"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader takes a :class:`chipbench.trace_reduce.Run` and returns a number,
or ``None`` where the run holds nothing to read; the harness then leaves the
metric out of the result line.
"""
from __future__ import annotations

import statistics


def mean_span_ms(run, name: str):
    d = run.span_ms(name)
    return statistics.fmean(d) if d else None


def window_work(run) -> dict:
    """The reference's work counts summed over the window's steps."""
    total = {}
    for counts in run.window["counts"]:
        for k, v in run.reference.work(run.config, counts).items():
            total[k] = total.get(k, 0.0) + v
    return total
