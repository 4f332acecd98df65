"""Seconds from process start to the first timed request: data generation,
ingest, plan build, payload pool, compilation or cache loads, warm-up."""


def read(run):
    return run["setup_s"]
