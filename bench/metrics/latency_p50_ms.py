"""Median request latency over every request of the window, from its scheduled arrival."""

from bench import readers


def read(run):
    return readers.latency_percentile(run, 50)
