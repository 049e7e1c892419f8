"""Seconds from process start to the first timed request: corpus, host copy, build, save, register, warm-up and compilation."""


def read(run):
    return run.setup_s
