"""Share of the traced window in which no program ran on the device, in
percent (profiler trace).  Nothing when the trace holds no device."""


def read(ctx):
    return None if ctx.profile is None else ctx.profile.idle_pct()
