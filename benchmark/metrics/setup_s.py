"""Seconds from the process's start to the window's start: imports,
corpus, server, warm-up and any compilation."""


def read(run):
    return run["setup_s"]
