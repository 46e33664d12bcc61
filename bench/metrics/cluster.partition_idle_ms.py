"""Device idle per traced epoch on the partition nodes' chips (every chip
but the master's), mean over them: the stretches inside the traced
``engine.run_epoch`` annotations in which such a chip ran nothing, which
is how long partition nodes wait on the master and on the host."""
from starbench import devtrace, mesh


def read(ctx):
    events = ctx["device_events"]
    master = mesh.master_plane(events)
    others = [p for p in devtrace.devices(events) if p != master]
    if master is None or not others or not ctx["traced"]:
        return None
    ns = sum(mesh.idle_ns_in_calls(events, p) for p in others) / len(others)
    return ns / len(ctx["traced"]) / 1e6
