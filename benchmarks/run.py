"""One run of one cell of the benchmark:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one touch of JAX.  Everything about a cell is data found by
name from ``BENCHMARK.json``: its configuration file, its traffic file
(``traffic/<mix>.json``), the kind of run the traffic file names
(``drivers/<kind>.py``), the model family's builder and plain reference
(``builders/<family>.py``), and one reader per per-layer metric
(``metrics/<metric>.py``).  No cell, configuration or metric is named in
code: a later PR adds files and entries, and edits nothing here.

Refuses to run without a TPU, or with fewer chips than the cell asks
for: no number from a CPU is ever printed under a device metric's name.
The last line of standard output is the one JSON object the driver
reads; per-run detail goes to earlier lines and ``benchmarks/out/``.
"""

import time

T_START = time.perf_counter()       # process start, for setup_s

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import importlib                    # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    workload: str
    config: dict
    traffic: dict
    builder: object
    seed: int
    seconds: float
    trace: bool
    chips: int
    peaks: dict
    devices: list
    trace_dir: str
    t_start: float
    log: object

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest of the cell's chips (0 where
        the backend reports none)."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}",
          flush=True)


class Files:
    """Finds the benchmark's data and code files by name: under each of
    the manifest's ``paths`` (relative to the manifest), then beside this
    file.  A missing file is an error that names it."""

    def __init__(self, manifest_path: str):
        self.base = os.path.dirname(os.path.abspath(manifest_path))
        with open(manifest_path) as f:
            self.manifest = json.load(f)
        self.dirs = [os.path.join(self.base, p)
                     for p in self.manifest["paths"]] + [HERE]

    def find(self, rel: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, rel)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"{rel} not found under any of {self.dirs}")

    def json(self, path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def module(self, rel: str):
        path = self.find(rel)
        stem = os.path.basename(path)[:-3].replace(".", "_")
        own = path.startswith(HERE + os.sep)
        # the benchmark's own modules keep their package names, so a
        # driver imported here and by a test is one module
        name = ".".join(["benchmarks",
                         *os.path.relpath(os.path.dirname(path),
                                          HERE).split(os.sep), stem]) \
            if own else f"benchmarks_ext.{stem}"
        if name in sys.modules:
            return sys.modules[name]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        return mod

    def entry(self, section: str, name: str) -> dict:
        for e in self.manifest[section]:
            if e["name"] == name:
                return e
        raise KeyError(f"no entry {name!r} under {section!r} of the "
                       f"manifest (has: "
                       f"{[e['name'] for e in self.manifest[section]]})")

    def metrics_of(self, section: str, workload: str) -> list:
        return [m for m in self.manifest[section]
                if "workloads" not in m or workload in m["workloads"]]


def reduce_trace(ctx: Context, result: dict) -> dict:
    """Load the traced slice and reduce it to what ``device`` and
    ``breakdown`` of the last line carry; leaves the loaded trace in
    ``result`` for the per-layer readers."""
    from benchmarks.lib import xplane
    path = xplane.find_xplane(ctx.trace_dir)
    trace = xplane.load(path)
    marks = [e for e in trace["host"] if e[0] == "bench.window"]
    used = sorted(trace["devices"])[:ctx.chips]
    ops0 = trace["devices"][used[0]]["ops"] if used else []
    if marks:
        t0, t1 = marks[0][1], marks[0][1] + marks[0][2]
    elif ops0:
        t0 = min(s for _, s, _ in ops0)
        t1 = max(s + d for _, s, d in ops0)
    else:
        raise RuntimeError(f"neither the window's mark nor a device "
                           f"operation in {path}")
    result["trace"], result["trace_window_ns"] = trace, (t0, t1)
    # no device plane (the CPU rehearsal): busy 0, which main() refuses
    busy = [xplane.busy_ns(xplane.clip(trace["devices"][d]["ops"], t0, t1))
            for d in used] or [0.0]
    ops0 = xplane.clip(ops0, t0, t1)
    host = [e for e in trace["host"]
            if e[2] > 0 and not e[0].startswith("$")
            and e[0] != "bench.window"]
    log(f"trace: {path} window {(t1 - t0) / 1e9:.3f}s devices {used} "
        f"ops on device 0: {len(ops0)} host spans: {len(host)}")
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "breakdown": {
            "device_ops": xplane.top_ops(ops0, 10),
            "idle_gaps": xplane.idle_gaps(ops0, t0, t1, host, 10),
        },
    }


def make_context(files: Files, workload: str, seed: int, seconds: float,
                 trace: bool, devices: list, peaks: dict, out_dir: str):
    """Resolve a cell's files by name.  Returns ``(context, driver)``."""
    cell = files.entry("workloads", workload)
    cfg_entry = files.entry("configs", cell["config"])
    config = files.json(os.path.join(files.base, cfg_entry["file"]))
    mix = files.json(files.find(f"traffic/{cell['traffic']}.json"))
    ctx = Context(workload=workload, config=config, traffic=mix,
                  builder=files.module(f"builders/{config['builder']}.py"),
                  seed=seed, seconds=seconds, trace=trace,
                  chips=cell["chips"], peaks=peaks,
                  devices=devices[:cell["chips"]],
                  trace_dir=os.path.join(out_dir, "trace", workload),
                  t_start=T_START, log=log)
    log(f"cell {workload}: config {cell['config']} traffic "
        f"{cell['traffic']} ({mix['kind']}) chips {cell['chips']} "
        f"seed {seed} seconds {seconds} trace {int(trace)}")
    return ctx, files.module(f"drivers/{mix['kind']}.py")


def run_cell(files: Files, workload: str, seed: int, seconds: float,
             trace: bool, devices: list, peaks: dict,
             out_dir: str) -> dict:
    """Run one cell on ``devices`` and return the last line's object.
    The TPU check is ``main``'s alone: the tests call this on the CPU at
    a tiny size."""
    ctx, driver = make_context(files, workload, seed, seconds, trace,
                               devices, peaks, out_dir)
    if trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    result = driver.run(ctx)
    result.update(peaks=peaks, chips=ctx.chips, log=log)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    values = {"setup_s": result["setup_s"], **result["end_to_end"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if not trace:
        wanted = files.metrics_of("end_to_end", workload)
    else:
        reduced = reduce_trace(ctx, result)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = reduced["breakdown"]
        wanted = files.metrics_of("per_layer", workload)
        for m in wanted:
            values[m["name"]] = files.module(
                f"metrics/{m['name']}.py").read(result)
    # a reader that finds nothing to read returns nothing, and the
    # metric is left out of the line
    line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in wanted if values.get(m["name"]) is not None}
    line["device"] = device
    log(f"end to end: {json.dumps(values, sort_keys=True)}")
    log(f"detail: {json.dumps(result['detail'], sort_keys=True)}")
    log(f"memory: peak {result['memory_peak_bytes']} bytes at the "
        f"window's end; {ctx.memory_peak()} at exit")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"{workload}.seed{seed}.trace{int(trace)}.json"),
            "w") as f:
        json.dump({"line": line, "values": values,
                   "checks": result.get("checks"),
                   "detail": result["detail"]}, f, indent=1, sort_keys=True)
    return line


def demand_tpu(chips: int):
    """The visible TPU devices and their peaks, with the compile cache
    on; exits non-zero, with no result, where jax finds no TPU or fewer
    chips than ``chips``."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"benchmarks: needs a TPU, jax found "
                 f"{dev.platform}:{dev.device_kind}; nothing was run")
    if len(devices) < chips:
        sys.exit(f"benchmarks: the cell needs {chips} chips, jax found "
                 f"{len(devices)}; nothing was run")
    from benchmarks.lib.peaks import chip_peaks
    peaks = chip_peaks(dev.device_kind)
    # the compile cache lives under the checkout (or where
    # JAX_COMPILATION_CACHE_DIR says): only the first run of a cell in a
    # checkout compiles.  Sub-second programs are cached too: PR 21 saw
    # them compile again in every process.
    from paddle_tpu.device import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} entries={entries}")
    # an array a jitted program closes over is compiled in as a constant:
    # at these widths that is gigabytes of host memory while it lowers
    jax.config.update("jax_captured_constants_warn_bytes", 64 << 20)
    return devices, peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    files = Files(os.path.join(ROOT, "BENCHMARK.json"))
    cell = files.entry("workloads", args.workload)

    devices, peaks = demand_tpu(cell["chips"])
    line = run_cell(files, args.workload, args.seed, args.seconds,
                    bool(args.trace), devices, peaks,
                    os.path.join(HERE, "out"))
    if args.trace and not line["device"]["busy_s"] > 0:
        sys.exit("benchmarks/run.py: the trace shows no operation on the "
                 "device; no result")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
