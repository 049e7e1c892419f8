"""Share of each served row's two planted supporting passages found in its top 10, over every row of the window."""

from bench import readers


def read(run):
    return readers.support_recall(run, 10)
