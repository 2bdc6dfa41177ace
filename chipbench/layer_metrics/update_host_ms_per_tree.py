"""Host milliseconds inside ``update()`` per tree of the window: the mean of
the program's last ``window.trees`` ``boost_round`` spans."""
from lightgbm_tpu.obs import trace


def read(ctx, spec):
    n = int(ctx["window"]["trees"])
    spans = trace.spans("boost_round")  # empty with telemetry off
    if n <= 0 or len(spans) < n:
        return None
    return 1e3 * sum(s["dur"] for s in spans[-n:]) / n
