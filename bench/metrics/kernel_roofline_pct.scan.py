"""The analysis kernels' share of their roofline, in percent: the least
time the chip could take for the window's kernel calls (the reduction's own
bytes and operations from the shapes at each wrapper call, over the peaks
of ``bench/peaks.json``; bandwidth bounds every one of them) over the
device time of their jit programs in the profiler trace.  Nothing when the
trace holds no such program."""

PROGRAMS = ("jit_segment_sum_matrix", "jit_time_profile_matrix",
            "jit_pair_sum_matrix", "jit_histogram_counts")


def read(ctx):
    if ctx.profile is None or ctx.peaks is None:
        return None
    device_s = sum(ctx.profile.program_s(p) for p in PROGRAMS)
    least_s = ctx.roofline_s()
    if device_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / device_s
