"""Device milliseconds of Algorithm 2's node passes (``figaro.heads_tails``,
``figaro.join_children``, ``figaro.project``) per request completed in the
traced window."""

from bench import phases


def read(run):
    return phases.read_ms_per_request(run, phases.NODE_PASSES)
