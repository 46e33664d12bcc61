"""Device time of the partitioned-phase program per traced epoch."""
from starbench import devtrace

PROGRAMS = {"jit_run_partitioned"}


def read(ctx):
    t = devtrace.module_seconds(ctx["device_events"], PROGRAMS)
    if not t or not ctx["traced"]:
        return None
    return t / len(ctx["traced"]) * 1e3
