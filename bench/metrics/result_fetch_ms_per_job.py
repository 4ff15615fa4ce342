"""Host milliseconds per window job in the program's
``platform.fetch_result`` spans (``JobTicket.span_seconds``): each
dispatch's device-to-host copy of its result and the split into per-task
partials."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_job(run, "platform.fetch_result")
