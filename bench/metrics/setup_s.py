"""Set-up, s: process start to the window's start (TPU start, artifact
load or build, weights, warm-up)."""


def read(record):
    return record["setup_s"]
