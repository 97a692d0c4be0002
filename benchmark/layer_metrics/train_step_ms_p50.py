"""Median over the window's log windows of (time between two device-synced
log boundaries) / log_every."""

import statistics


def read(name, ctx):
    w = ctx["observed"].get("step_ms_windows")
    return float(statistics.median(w)) if w else None
