"""The 99th percentile of the commit latencies of the traced run's whole
window: scheduled arrival (open loop) or issue (closed loop) to the host
clock when the request's commit fence returned.  Reported without a
bound: a host stall of one to three seconds in some runs moves it by a
third or more."""


def read(ctx):
    return ctx["window"]["commit_p99_ms"]
