"""From a profiler trace to numbers: the device's busy union and idle share,
per-kernel sums, the biggest device operations and the longest idle gaps,
each under the harness span it fell in.

The arithmetic works on plain tuples so a hand-made event list can check it
(tests/test_trace_reduction.py):

    op   = (name, start_ns, duration_ns)      an operation on a device lane
    span = (name, start_ns, duration_ns)      a harness TraceAnnotation

`load()` reads those out of an `.xplane.pb` with `jax.profiler.ProfileData`:
operations from the "XLA Ops" line of every "/device:" plane, spans from the
host plane's events whose name starts with SPAN_PREFIX.  Both are on the
profiler's one clock.
"""
import glob
import os
import re
import shutil

SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"


# ---------------------------------------------------------------- arithmetic
def leaves(ops):
    """Drop operations that enclose others on the same lane (while, call,
    conditional): their children are the work, the gaps between children are
    not."""
    out = []
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    for i, (name, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and dur > 0 and nxt[1] < start + dur \
                and nxt[1] + nxt[2] <= start + dur:
            continue
        out.append((name, start, dur))
    return out


def union(intervals):
    """Merged, sorted [start, end) list of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy_ns(ops, lo, hi):
    """Nanoseconds of [lo, hi) in which some operation of `ops` ran (hand it
    leaves: an enclosing operation would cover its children's gaps)."""
    iv = clip([(s, s + d) for _, s, d in ops], lo, hi)
    return sum(b - a for a, b in union(iv))


def gaps(ops, lo, hi):
    """The idle intervals of [lo, hi): its complement of the busy union."""
    out, at = [], lo
    for a, b in union(clip([(s, s + d) for _, s, d in ops], lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def span_at(spans, a, b):
    """Name of the harness span that covers most of [a, b), or "outside"."""
    best, best_cover = "outside", 0
    for name, s, d in spans:
        cover = min(b, s + d) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def kernel_ns(ops, pattern, lo=None, hi=None):
    """Summed duration of the operations whose name matches `pattern` (a
    regular expression, searched) and that start inside [lo, hi)."""
    rx = re.compile(pattern)
    return sum(d for name, s, d in ops
               if rx.search(name) and (lo is None or lo <= s < hi))


def base_name(name):
    """One row a kind of operation.  The TPU trace names an operation by its
    whole HLO text, `%fusion.123 = f32[...] fusion(...)`: keep the
    instruction's name, without `%` and its number."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(ops, spans, lo, hi, n=10):
    """[[span/op, seconds], ...] — the operations that took most device time
    in the window, by the harness span they started in."""
    total = {}
    for name, s, d in ops:
        if lo <= s < hi:
            key = f"{span_at(spans, s, s + max(d, 1))}/{base_name(name)}"
            total[key] = total.get(key, 0) + d
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def top_gaps(ops, spans, lo, hi, n=10):
    """[[span, seconds], ...] — idle time of the window by the harness span
    the device was waiting under, longest first."""
    total = {}
    for a, b in gaps(ops, lo, hi):
        # a gap that runs across spans is split at their borders
        cuts = sorted({a, b, *(t for _, s, d in spans for t in (s, s + d)
                               if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            key = span_at(spans, x, y)
            total[key] = total.get(key, 0) + (y - x)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


# -------------------------------------------------------------- the reduction
class Reduced:
    """One traced stretch: `lanes` maps a device to its leaf operations,
    `spans` are the harness's own.  The window is the hull of the spans."""

    def __init__(self, lanes, spans):
        self.lanes = {dev: leaves(ops) for dev, ops in lanes.items()}
        self.spans = sorted(spans, key=lambda e: e[1])
        if not self.spans:
            raise ValueError("trace holds no harness span "
                             f"({SPAN_PREFIX}*): nothing to reduce")
        self.lo = self.spans[0][1]
        self.hi = max(s + d for _, s, d in self.spans)

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self):
        """Mean over the devices of the busy union inside the window."""
        if not self.lanes:
            return 0.0
        return sum(busy_ns(ops, self.lo, self.hi)
                   for ops in self.lanes.values()) / len(self.lanes) / 1e9

    @property
    def idle_pct(self):
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, pattern):
        """Mean over the devices of the matching kernels' time in the window."""
        if not self.lanes:
            return 0.0
        return sum(kernel_ns(ops, pattern, self.lo, self.hi)
                   for ops in self.lanes.values()) / len(self.lanes) / 1e9

    def spans_named(self, name):
        return [e for e in self.spans if e[0] == name]

    def busy_inside(self, span):
        """Mean over the devices of busy seconds inside one span."""
        _, s, d = span
        if not self.lanes:
            return 0.0
        return sum(busy_ns(ops, s, s + d)
                   for ops in self.lanes.values()) / len(self.lanes) / 1e9

    def breakdown(self):
        first = next(iter(self.lanes.values()), [])
        return {"device_ops": top_ops(first, self.spans, self.lo, self.hi),
                "idle_gaps": top_gaps(first, self.spans, self.lo, self.hi)}


def load(path):
    """Reduced <- an .xplane.pb file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    lanes, spans, host_ops = {}, [], []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if on_device:
                if line.name == OPS_LINE:
                    lanes[plane.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
                continue
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.duration_ns)))
                elif any(k == "hlo_op" for k, _ in e.stats):
                    host_ops.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    if not lanes and host_ops:
        # a CPU rehearsal has no device plane: XLA:CPU's operations stand in
        # so that the path runs; run.py prints no timing from it
        lanes["/host:CPU"] = host_ops
    return Reduced(lanes, spans)


class Profiler:
    """jax.profiler round a short stretch; the directory is removed once the
    trace is reduced (or copied to `keep` first, for the recorded test
    trace)."""

    def __init__(self, directory, keep=None):
        self.dir = directory
        self.keep = keep

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # the harness's own annotations and the device lanes are all that is
        # read: no Python call tracer, no HLO dump — smaller and less in the
        # host's way
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            if not files:
                raise RuntimeError(f"profiler wrote no .xplane.pb under "
                                   f"{self.dir}")
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(files[0], os.path.join(self.keep,
                                                   "trace.xplane.pb"))
            return load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
