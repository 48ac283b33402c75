"""Operations acknowledged per frontend ``step()`` that did work, counted
by the harness over the window."""


def read(run):
    if run.steps_with_work <= 0:
        return None
    return len(run.window_ops) / run.steps_with_work
