"""Prefill scheduling rounds run in the window (`scheduling_rounds` after
minus before) per request due in it."""


def read(run):
    if not run.records:
        return None
    return run.rounds / len(run.records)
