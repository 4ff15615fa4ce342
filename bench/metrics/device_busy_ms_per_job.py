"""Device busy time in the traced window (the union of its operations'
intervals) per job finished there, in ms."""


def read(run):
    if run.trace is None or not run.jobs:
        return None
    return run.trace.busy_s / len(run.jobs) * 1e3
