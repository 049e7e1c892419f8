"""Median per-batch search time of the window (host clock around index.search and its blocking copy)."""

from bench import readers


def read(run):
    return readers.search_ms(run)
