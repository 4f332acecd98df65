"""Device busy milliseconds in the traced window per request completed in
it: all device work of a request (Algorithm 2, post-processing,
downstream), with the batch's share of padding."""

from bench.tracing import completed_in


def read(run):
    trace = run["trace"]
    done = completed_in(run["result"]["requests"], run["trace_span"])
    if not trace or not trace["devices"] or not done:
        return None
    return 1e3 * trace["busy_s"] / done
