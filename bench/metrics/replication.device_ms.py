"""Device time per traced epoch of the programs that bring the replica up
to the master: the ordered replay of the partitioned stream, the Thomas
write-rule apply of the single-master stream, and its index replay."""
from starbench import devtrace

PROGRAMS = {"jit_replay_partitioned", "jit_thomas_apply_batch",
            "jit_replay_index_rounds"}


def read(ctx):
    t = devtrace.module_seconds(ctx["device_events"], PROGRAMS)
    if not t or not ctx["traced"]:
        return None
    return t / len(ctx["traced"]) * 1e3
