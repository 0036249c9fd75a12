"""Median latency over every request due in the window, from its due
time to the return of its answer; a failed request counts as later than
all others."""
from bench.traffic import latency_percentile_ms


def read(run):
    return latency_percentile_ms(run.records, 50)
