"""Share of the wave lanes dispatched for the window's jobs that carried
a task: sum of ``JobTicket.n_tasks`` over sum of ``JobTicket.wave_lanes``
(padding lanes included, a shared wave split by task share), in %."""


def read(run):
    lanes = [getattr(j.ticket, "wave_lanes", None) for j in run.jobs]
    if not lanes or None in lanes or sum(lanes) <= 0:
        return None
    return sum(j.ticket.n_tasks for j in run.jobs) / sum(lanes) * 100.0
