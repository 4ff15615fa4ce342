"""Host milliseconds per window job in the program's ``platform.upload``
spans (``JobTicket.span_seconds``): the per-task block uploads, or a
wave's slot and seed vectors, built and sent."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_job(run, "platform.upload")
