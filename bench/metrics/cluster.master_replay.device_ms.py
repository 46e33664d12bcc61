"""Device time per traced epoch of the full replica's replay of every
node's partitioned op stream, on the master's chip alone (the chip that
ran it)."""
from starbench import mesh


def read(ctx):
    events = ctx["device_events"]
    plane = mesh.master_plane(events)
    if plane is None or not ctx["traced"]:
        return None
    t = mesh.seconds_on(events, plane, {mesh.FULL_REPLAY})
    return t / len(ctx["traced"]) * 1e3
