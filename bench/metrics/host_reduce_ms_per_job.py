"""Host milliseconds per window job in the program's ``platform.reduce``
spans (each leaf's combines on the reduce tree's thread) and its
``platform.finalize`` span (the root, ``finalize_stats``, completion),
from ``JobTicket.span_seconds``."""

from bench import program_spans


def read(run):
    return program_spans.ms_per_job(run, "platform.reduce",
                                    "platform.finalize")
