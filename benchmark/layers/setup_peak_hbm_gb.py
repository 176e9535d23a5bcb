"""The allocator's peak before any training program ran: `hbm_peak_bytes`
(the largest over the cell's devices) on the FIRST `Runtime::Compile` record
of a `fused_iter` entry - read when that compile ended, so it holds set-up's
transients (ship, pack, bind) and nothing of training.  Equal to
`peak_hbm_gb` where set-up sets the run's peak.  The log line gives the
in-use and peak readings of every set-up record that carries them."""
import poll_timeline
import program_spans

NAME = "setup_peak_hbm_gb"
UNIT = "GB"
LAYER = "basic"
MOVES = "peak_hbm_gb"
ALSO = ("Dataset::Ship", "GBDT::ShardBind")


def read(run):
    compiles = poll_timeline.setup_compiles(run)
    first = poll_timeline.of_iteration(compiles or [])[:1]
    if not first:
        return None
    marks = [r for name in ALSO
             for r in poll_timeline.with_hbm(
                 program_spans.in_setup(run, name) or [])] + first
    run.say(f"{NAME} (in use / peak, GB): " + "; ".join(
        f"{r.name} {r.args[poll_timeline.HBM_IN_USE] / 1e9:.6f} / "
        f"{r.args[poll_timeline.HBM_PEAK] / 1e9:.6f}"
        for r in sorted(marks, key=poll_timeline.end_ns)))
    return first[0].args[poll_timeline.HBM_PEAK] / 1e9
