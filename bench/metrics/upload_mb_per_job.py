"""Mean host-to-device bytes per window job (``JobTicket.bytes_uploaded``),
in MB: whole blocks on the per-task path, slot and seed vectors on the
wave path."""


def read(run):
    if not run.jobs:
        return None
    return sum(j.ticket.bytes_uploaded for j in run.jobs) / len(run.jobs) / 1e6
