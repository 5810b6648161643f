"""Seconds from process start to the first timed frame: imports, table
build and upload, compilation or cache load, session fill, warm-up."""


def read(run):
    return run["setup_s"]
