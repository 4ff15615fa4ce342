"""Median time a window job waited between submit and its first task
starting (``JobTicket.queue_wait``): admission and the scheduler."""

import statistics


def read(run):
    waits = [j.ticket.queue_wait for j in run.jobs
             if j.ticket.queue_wait is not None]
    return statistics.median(waits) * 1e3 if waits else None
