"""Host wall time of the median epoch, fence to fence, over the window's
epochs that the profiler did not cover.  The median keeps out the epoch of
the node kill, which holds the doomed epoch, the recovery and the
re-execution."""
import statistics


def read(ctx):
    fences = [ctx["fences"][0]] + [e["t_fence"] for e in ctx["epochs"]]
    if len(fences) < 2:
        return None
    return statistics.median(b - a for a, b in zip(fences, fences[1:])) * 1e3
