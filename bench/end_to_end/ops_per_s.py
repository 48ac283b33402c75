"""Acknowledged operations (reads answered, writes acknowledged durable)
over the whole measured window."""


def read(run):
    if run.window_s <= 0 or not run.window_ops:
        return None
    return len(run.window_ops) / run.window_s
