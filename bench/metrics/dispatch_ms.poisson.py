"""Median repro.dispatch span of the window: index.search called until it returns (program spans)."""

from bench import spans


def read(run):
    return spans.dispatch_ms(spans.records(run))
