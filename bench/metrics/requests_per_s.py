"""Requests completed per second over the closed-loop window: completions
after the first one, up to the first batch boundary ``--seconds`` or more
later, over the time between those two completions (`load.batch_window`)."""


def read(run):
    window = run["result"].get("window")
    if not window:
        return None
    start, end, completed = window
    return completed / (end - start)
