"""A predict call's wall time minus the device's busy time inside it, mean
over the traced calls: float64 re-bin, node tables, pack, ship, readback."""
NAME = "predict_host_ms_per_call"
UNIT = "ms"
LAYER = "basic"
MOVES = "score_rows_per_s"


def read(run):
    calls = run.reduced.spans_named("bench.predict") if run.reduced else []
    if not calls:
        return None
    host = [d / 1e9 - run.reduced.busy_inside((n, s, d)) for n, s, d in calls]
    return 1e3 * sum(host) / len(host)
