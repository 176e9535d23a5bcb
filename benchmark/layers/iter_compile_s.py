"""Backend compile time of the fused iteration alone: those of set-up's
`Runtime::Compile` records with `cache: miss` whose `entry` is `fused_iter`
(one program a dense job, two a sampled one).  The log line gives each
record's trace number, its tracing and lowering time and, from an entry's
second trace on, the signature that caused it."""
import poll_timeline

NAME = "iter_compile_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "setup_s"


def read(run):
    compiles = poll_timeline.setup_compiles(run)
    if compiles is None:
        return None
    paid = poll_timeline.of_iteration(poll_timeline.missed(compiles))
    run.say(f"{NAME}: " + ("; ".join(
        f"trace {r.args.get('trace')}: backend {r.duration_ns / 1e9:.3f} s, "
        f"tracing {r.args.get('trace_ns', 0) / 1e9:.3f}, lowering "
        f"{r.args.get('lower_ns', 0) / 1e9:.3f}"
        + (f", signature {r.args['signature']}" if "signature" in r.args
           else "") for r in paid) or "no fused iteration compiled"))
    return sum(r.duration_ns for r in paid) / 1e9
