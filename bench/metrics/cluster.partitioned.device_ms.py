"""Device time of the cluster's partitioned-phase program (one execution
per op-stream slab) per traced epoch, mean over the chips."""
from starbench import devtrace

PROGRAMS = {"jit_cluster_partitioned"}


def read(ctx):
    t = devtrace.module_seconds(ctx["device_events"], PROGRAMS)
    if not t or not ctx["traced"]:
        return None
    return t / len(ctx["traced"]) * 1e3
