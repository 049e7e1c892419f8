"""Query rows completed in the window per second of it."""

from bench import readers


def read(run):
    return readers.throughput_qps(run)
