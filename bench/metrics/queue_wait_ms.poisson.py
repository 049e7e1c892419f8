"""Median over the window's requests of admission to the dispatch of their first batch (program request records)."""

from bench import spans


def read(run):
    return spans.queue_wait_ms(spans.records(run))
