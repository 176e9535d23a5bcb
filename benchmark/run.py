"""The benchmark's one command: run one cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`; everything that belongs
to it is found by name: configs/<config>.json, traffic/<traffic>.json,
generators/<generator>.py, loops/<loop>.py, layers/<metric>.py.  This file
holds no cell's, no configuration's and no metric's name.  The last line of
standard output is the result (see README.md); anything before it is log.

It needs a TPU with at least the cell's `chips`; without one it exits 2 and
prints no result.  `--rehearse` (for benchmark/tests only) admits the CPU at
the configuration's tiny `rehearse` size and prints every value as null.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
CACHE = HERE / ".cache"


def say(msg):
    print(f"[{time.time() - T_START:7.1f}s] {msg}", flush=True)


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class CompileClock:
    """What JAX itself reports of compilation (chip_smoke.py's clock):
    seconds by event, and how many programs were compiled or fetched from
    the persistent cache — the count that must not move inside a window."""

    def __init__(self):
        import jax.monitoring
        self.secs = {}
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if "compil" in event or "cache" in event:
            self.secs[event] = self.secs.get(event, 0.0) + duration
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")):
            self.programs += 1

    @property
    def backend_s(self):
        return sum(v for k, v in self.secs.items()
                   if k.endswith("backend_compile_duration"))


class Run:
    """What a loop and a per-layer reader are handed."""

    def __init__(self, args, cell, config, traffic, clock, devices):
        self.args = args
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.clock = clock
        self.devices = devices
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.setup = {}          # the split of set-up, seconds by part
        self.spans = {}          # the harness's own measurements, by name
        self.reduced = None      # trace.Reduced of the traced stretch
        self.window_start = None
        self.say = say
        self.generator = load_module(
            HERE / "generators" / f"{self.sized('generator')}.py")
        self.peaks = json.loads((HERE / "peaks.json").read_text())

    def sized(self, key):
        """A configuration's value, or its `rehearse` override."""
        if self.rehearse and key in self.config.get("rehearse", {}):
            return self.config["rehearse"][key]
        return self.config[key]

    def mix(self, key):
        """A traffic parameter, or its `rehearse` override."""
        if self.rehearse and key in self.traffic.get("rehearse", {}):
            return self.traffic["rehearse"][key]
        return self.traffic[key]

    def make(self, rows, stream=0):
        return self.generator.make(self.seed, rows, self.config["shape"],
                                   stream=stream)

    def start_window(self):
        """The first measured instant: set-up ends here."""
        self.window_start = time.time()
        self.programs_at_start = self.clock.programs

    def compiled_in_window(self):
        return self.clock.programs - self.programs_at_start

    def profiler(self):
        from trace_reduction import Profiler
        return Profiler(str(CACHE / "trace" / self.cell["name"]),
                        keep=self.args.keep_trace)

    def peak(self):
        kind = self.devices[0].device_kind
        if kind not in self.peaks["device_kinds"]:
            raise SystemExit(f"run.py: no published peaks for device kind "
                             f"{kind!r} in benchmark/peaks.json")
        return self.peaks["device_kinds"][kind]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--manifest", default=str(REPO / "BENCHMARK.json"),
                    help="another manifest (benchmark/tests only)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace here before it is deleted")
    args = ap.parse_args()

    manifest = json.loads(Path(args.manifest).read_text())
    cell = named(manifest["workloads"], args.workload, "workload")
    config_entry = named(manifest["configs"], cell["config"], "config")
    config = json.loads((REPO / config_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"run.py: needs a TPU; JAX found platform {platform!r} "
              f"({devices[0].device_kind} x {len(devices)})", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} chips; JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    sys.path.insert(0, str(REPO))
    sys.path.insert(0, str(HERE))
    clock = CompileClock()
    from lightgbm_tpu import runtime
    cache_dir = runtime.configure_compile_cache()
    # keep every program, however quickly it compiled: a later run of the
    # cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"cell {cell['name']} seed {args.seed} on {platform} "
        f"{devices[0].device_kind} x {len(devices)}; compile cache {cache_dir}")

    run = Run(args, cell, config, traffic, clock, devices)
    loop = load_module(HERE / "loops" / f"{traffic['loop']}.py")
    result = loop.run(run)

    measured = dict(result["metrics"])
    measured["setup_s"] = run.window_start - T_START
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    measured["peak_hbm_gb"] = max(peaks) / 1e9
    say("set-up split (s): " + json.dumps(run.setup))
    say("checks: " + json.dumps(result["checks"]))

    metrics = {}
    if not run.trace:
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in measured:
                metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = load_module(HERE / "layers" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(peaks)}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if run.trace and run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        line["breakdown"] = run.reduced.breakdown()
    if run.rehearse:
        # a CPU run gives no time, rate or share: names only
        for m in metrics.values():
            m["value"] = None
        for k in ("busy_s", "window_s"):
            if k in device:
                device[k] = None
        line.pop("breakdown", None)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
