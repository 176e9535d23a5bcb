"""Sum of jax.monitoring backend_compile_duration during set-up (near 0 when
every program comes from the persistent cache)."""
NAME = "compile_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "setup_s"


def read(run):
    return run.setup.get("compile_s")
