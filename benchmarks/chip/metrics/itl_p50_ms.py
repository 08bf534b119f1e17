"""Median of every gap between consecutive output tokens of every
request, for the gaps that end inside the window."""
from harness import reading


def read(rec):
    v = reading.percentile(reading.itl_gaps(rec), 50)
    return None if v is None else 1e3 * v
