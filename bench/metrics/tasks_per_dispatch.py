"""Tasks per device dispatch over the window's jobs: sum of
``JobTicket.n_tasks`` over sum of ``JobTicket.device_dispatches`` (a wave
shared by two jobs counts once for each)."""


def read(run):
    dispatches = sum(j.ticket.device_dispatches for j in run.jobs)
    if not dispatches:
        return None
    return sum(j.ticket.n_tasks for j in run.jobs) / dispatches
