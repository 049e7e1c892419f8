"""Median over the window's batches that had work waiting of previous fetch end to this dispatch start (program batch records)."""

from bench import spans


def read(run):
    return spans.turnaround_ms(spans.records(run))
