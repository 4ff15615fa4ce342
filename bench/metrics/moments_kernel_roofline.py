"""Share of the roofline that the ``subsample_stats_wave`` kernel reaches
in the traced window: the least time the window's jobs need (true bytes
of the distinct samples each task draws, over the chip's HBM bandwidth,
or their operations over its peak, whichever is longer) over the
kernel's summed device time."""

from bench import roofline

KERNEL = "subsample_stats_wave"


def read(run):
    if run.trace is None or run.peaks is None or not run.jobs:
        return None
    kernel_s = run.trace.seconds_of(KERNEL)
    if kernel_s <= 0:
        return None
    q = run.query
    nbytes, flops = roofline.moments_least_work(
        run.dataset.lengths, run.dataset.values.itemsize, run.knee_bytes,
        [j.seed for j in run.jobs], q["draws"] * q["draw_size"])
    least, bound = roofline.least_seconds(nbytes, flops, run.peaks)
    run.note(f"moments_kernel_roofline: {nbytes:.0f} least bytes, "
             f"{flops:.0f} least flops, bound by {bound}; kernel "
             f"{kernel_s:.6f} s over {len(run.jobs)} jobs")
    return least / kernel_s * 100.0
