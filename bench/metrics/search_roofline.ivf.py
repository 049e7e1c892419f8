"""Least time of the traced IVF batches (bench/work.py) over device busy time, in %."""

from bench import readers


def read(run):
    return readers.ivf_roofline(run)
