"""Backend compile time of set-up by the program's own records: the summed
duration of set-up's `Runtime::Compile` records with `cache: miss` (one
record for every program the process compiled; a program fetched from the
persistent cache is `cache: hit` and not summed).  The inside twin of
`compile_s`, which times the same events from outside and names no program:
the log line here says which entry's program cost what."""
import poll_timeline

NAME = "program_compile_s"
UNIT = "s"
LAYER = "runtime"
MOVES = "setup_s"


def by_entry(compiles):
    """[(entry or None, programs, backend ns, trace ns, lower ns)], the
    costliest first."""
    rows = {}
    for r in compiles:
        row = rows.setdefault(r.args.get("entry"), [0, 0, 0, 0])
        row[0] += 1
        row[1] += r.duration_ns
        row[2] += r.args.get("trace_ns", 0)
        row[3] += r.args.get("lower_ns", 0)
    return sorted(((k, *v) for k, v in rows.items()), key=lambda r: -r[2])


def read(run):
    compiles = poll_timeline.setup_compiles(run)
    if compiles is None:
        return None
    paid = poll_timeline.missed(compiles)
    run.say(f"{NAME}: {len(paid)} programs compiled, "
            f"{len(compiles) - len(paid)} fetched from the cache; by entry "
            "(programs, backend s, tracing s, lowering s): " + "; ".join(
                f"{entry or 'no entry (eager)'} {n}, {ns / 1e9:.3f}, "
                f"{tr / 1e9:.3f}, {lo / 1e9:.3f}"
                for entry, n, ns, tr, lo in by_entry(paid)[:8]))
    return sum(r.duration_ns for r in paid) / 1e9
