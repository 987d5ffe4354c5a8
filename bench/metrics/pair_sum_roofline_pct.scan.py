"""``pair_sum``'s share of its roofline, in percent: the least time the
chip could take for the window's ``pair_sum_matrix`` calls (the
reduction's own bytes and operations from the shapes at each wrapper call,
``bench.probes.kernel_bytes_ops``, over the peaks of ``bench/peaks.json``)
over the device time of the ``jit_pair_sum_matrix`` program in the
profiler trace, whatever that program runs besides the kernel.  Nothing
without a profile, peaks or such a program."""

from bench.probes import kernel_bytes_ops

WRAPPER = "pair_sum_matrix"
PROGRAM = "jit_pair_sum_matrix"


def read(ctx):
    if ctx.profile is None or ctx.peaks is None:
        return None
    device_s = ctx.profile.program_s(PROGRAM)
    least_s = 0.0
    for name, shapes in ctx.kernel_calls():
        if name == WRAPPER:
            by, ops = kernel_bytes_ops(name, shapes)
            least_s += max(by / ctx.peaks["hbm_bytes_per_s"],
                           ops / ctx.peaks["flops_per_s"])
    if device_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / device_s
