"""Device bytes of the query class's arena (mean ``JobTicket.arena_bytes``
over the window's jobs: data and months planes, padding included) over
the true bytes of the data set's observations and their months."""


def read(run):
    arena = [getattr(j.ticket, "arena_bytes", None) for j in run.jobs]
    if not arena or None in arena:
        return None
    ds = run.dataset
    true = ds.values.nbytes + (0 if ds.month_values is None
                               else ds.month_values.nbytes)
    mean = sum(arena) / len(arena)
    return mean / true if mean > 0 and true > 0 else None
