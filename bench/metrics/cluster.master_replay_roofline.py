"""Share of the full replica's replay time on the master's chip that the
least traffic of the replayed streams would take at the chip's peak HBM
bandwidth: the bytes of the traced epochs' committed partitioned writes
(``starbench.mesh.replay_bytes``) over the bandwidth, divided by the
program's device time."""
from starbench import mesh


def read(ctx):
    events = ctx["device_events"]
    plane = mesh.master_plane(events)
    if plane is None:
        return None
    t = mesh.seconds_on(events, plane, {mesh.FULL_REPLAY})
    if not t:
        return None
    need = sum(mesh.replay_bytes(e["batch"], e["p_committed"], ctx["n_cols"])
               for e in ctx["traced"])
    return need / ctx["peaks"]["hbm_bytes_per_s"] / t * 100
