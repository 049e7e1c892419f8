"""Query rows per micro-batch over the window (engine counters queries_served / batches_served)."""

from bench import readers


def read(run):
    return readers.batch_rows(run)
