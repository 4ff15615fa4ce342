"""Share of the roofline that the grid statistic (``monthly_mean``,
``alod``) reaches in the traced window: the least time the window's jobs
need (true bytes of the distinct observations each task draws, with
their months, over the chip's HBM bandwidth, or two operations per drawn
value over its peak, whichever is longer) over the device's busy time
(the union of its operations), whatever program computes it."""

from bench import grid_roofline, roofline


def read(run):
    if run.trace is None or run.peaks is None or not run.jobs:
        return None
    if run.query["statistic"] not in grid_roofline.STATISTICS:
        return None
    busy_s = run.trace.busy_s
    if busy_s <= 0:
        return None
    nbytes, flops = grid_roofline.grid_least_work(
        run.dataset, run.knee_bytes, [j.seed for j in run.jobs], run.query)
    least, bound = roofline.least_seconds(nbytes, flops, run.peaks)
    run.note(f"grid_stat_roofline: {nbytes:.0f} least bytes, {flops:.0f} "
             f"least flops, bound by {bound}; device busy {busy_s:.6f} s "
             f"over {len(run.jobs)} jobs")
    return least / busy_s * 100.0
