"""Least time of the traced exact-scan batches (bench/work.py) over device busy time, in %."""

from bench import readers


def read(run):
    return readers.exact_roofline(run)
