"""What training holds: the largest `hbm_in_use_bytes` over the window's
`GBDT::FlagPoll` records.  The program reads it at the poll BEFORE the
blocking fetch, with the device's launches in flight, as the largest over the
cell's devices.  The log line gives every poll's reading beside the
allocator's peak at that moment (`hbm_peak_bytes`)."""
import poll_timeline

NAME = "train_hbm_gb"
UNIT = "GB"
LAYER = "models.gbdt"
MOVES = "peak_hbm_gb"


def read(run):
    polls = poll_timeline.window_polls(run)
    if polls is None:
        return None
    run.say(f"{NAME} (in use / peak at the poll, GB): " + "; ".join(
        f"at {r.args['iteration']}: "
        f"{r.args[poll_timeline.HBM_IN_USE] / 1e9:.6f} / "
        f"{r.args[poll_timeline.HBM_PEAK] / 1e9:.6f}" for r in polls))
    return max(r.args[poll_timeline.HBM_IN_USE] for r in polls) / 1e9
