"""Seconds from process start to the start of the window."""


def read(run, params):
    return run["setup_s"]
