"""Slices of a measured window, and the statistics read from them.

A window is a run of whole units of work whose completion times were
observed (training steps, decode laps).  The rate a cell reports is all the
work completed in the window over all its time (``mean_rate``): a stall
inside the window is in it.  The window is also cut into equal slices of
``slice_units`` consecutive completions; the median slice
(``mx.callback.Speedometer(batch_size, frequent)`` made robust) is what a
steady run would read, and ``stall_share`` = 1 - mean/median is what the
excursions cost.  Both are printed by every run.
"""
from __future__ import annotations

import statistics

MIN_SLICES = 12


def cut(edges, work):
    """Rates of the slices between consecutive ``edges`` (seconds, ascending);
    ``work[i]`` is the work completed between edge i and edge i+1."""
    if len(edges) != len(work) + 1:
        raise ValueError("need one more edge than slices")
    rates = []
    for i, w in enumerate(work):
        dt = edges[i + 1] - edges[i]
        if dt <= 0:
            raise ValueError("slice %d has no length" % i)
        rates.append(w / dt)
    return rates


def step_slices(done, slice_steps, work_per_step):
    """Cut a series of step completion times into whole slices.

    ``done[0]`` is the completion that opens the window (the last warm step);
    every later entry is one step completed inside it.  Completions after the
    last whole slice lie outside the window and are dropped.  Returns
    ``(edges, work)`` for `cut`."""
    n = (len(done) - 1) // slice_steps
    edges = [done[k * slice_steps] for k in range(n + 1)]
    return edges, [slice_steps * work_per_step] * n


def summary(edges, work):
    """The window as the cells report it."""
    rates = cut(edges, work)
    if len(rates) < MIN_SLICES:
        raise ValueError("window holds %d slices; the median is taken of at "
                         "least %d" % (len(rates), MIN_SLICES))
    median = statistics.median(rates)
    mean = sum(work) / (edges[-1] - edges[0])
    return {"slices": len(rates), "median_rate": median, "mean_rate": mean,
            "min_rate": min(rates), "max_rate": max(rates),
            "window_s": edges[-1] - edges[0],
            "stall_share_pct": 100.0 * (1.0 - mean / median)}


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation, and how many
    samples lie beyond it."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    value = v[lo] + (v[hi] - v[lo]) * (pos - lo)
    return value, sum(1 for x in v if x > value)
