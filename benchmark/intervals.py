"""The arithmetic of one window of step stamps.

A window is a list of ``time.perf_counter()`` stamps taken at the loop's
own synchronisation point (``on_step``: the loss of that step is on the
host). It runs from one step boundary to another, so it holds whole steps
and nothing else, and the rate is all of its work over all of its time:
a stall inside it lowers the rate by what it cost. The median interval and
the share of the wall it does not explain (``stall_pct``) stand beside the
rate and decide nothing.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

MIN_INTERVALS = 10


class TooFewIntervals(RuntimeError):
    """The window held fewer than MIN_INTERVALS step intervals: a failed
    run, not a noisier one."""


def intervals_of(stamps: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def summarize(stamps: Sequence[float], tokens_per_step: int,
              chips: int) -> Dict[str, float]:
    """Rate, median interval and stall share of one window of step
    stamps."""
    ivals = intervals_of(stamps)
    if len(ivals) < MIN_INTERVALS:
        raise TooFewIntervals(
            f"{len(ivals)} step intervals in the window, need "
            f"{MIN_INTERVALS}")
    median = statistics.median(ivals)
    wall = stamps[-1] - stamps[0]
    return {
        "n_intervals": len(ivals),
        "wall_s": wall,
        "train_tokens_per_s": tokens_per_step * len(ivals) / chips / wall,
        "step_interval_s": median,
        "stall_pct": 100.0 * (1.0 - len(ivals) * median / wall),
        "max_interval_s": max(ivals),
    }
