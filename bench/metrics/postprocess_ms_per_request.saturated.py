"""Device milliseconds of the post-processing of R0 (``figaro.postprocess``,
TSQR) per request completed in the traced window."""

from bench import phases


def read(run):
    return phases.read_ms_per_request(run, ("figaro.postprocess",))
