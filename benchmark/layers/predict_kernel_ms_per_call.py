"""Device time of the tree-walk kernel (pallas/predict_kernel.py
`_predict_kernel`) per traced predict call, from the device trace."""
NAME = "predict_kernel_ms_per_call"
UNIT = "ms"
LAYER = "pallas.predict_kernel"
MOVES = "score_rows_per_s"
# named after the jitted function round the pallas_call (predict_stream)
PATTERN = r"^%predict_stream[.\d]* = "


def read(run):
    calls = run.reduced.spans_named("bench.predict") if run.reduced else []
    took = run.reduced.kernel_s(PATTERN) if calls else 0
    return 1e3 * took / len(calls) if took else None
