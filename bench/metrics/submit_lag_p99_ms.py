"""How late the load generator submitted: the 99th percentile of submit
time minus due time over the requests due in the window (harness clock).
A starved generator reads high here, and must not pass for a fast
server."""


def read(run):
    return run.window.get("submit_lag_p99_ms")
