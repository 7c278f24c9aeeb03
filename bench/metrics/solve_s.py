"""Seconds per cold solve as the client sees it: (last answer - first
submit) / answers, over the closed loop's window."""


def read(run):
    return run.seconds_per_job()
