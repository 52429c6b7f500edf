#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It finds the cell in
``BENCHMARK.json``, its configuration and traffic files by name, the
runner by the traffic file's ``kind``, and each metric's reader by the
metric file's ``reader``; nothing here names a cell, a configuration or
a metric. The last line of standard output is the result object; the
numbers that decided ``correct`` are also the last lines of standard
error.
"""
from __future__ import annotations

import time
T_START = time.time()

import argparse       # noqa: E402
import importlib      # noqa: E402
import json           # noqa: E402
import os             # noqa: E402
import shutil         # noqa: E402
import sys            # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")


# -- data files ---------------------------------------------------------------
def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in spec['workloads']]}")


def load_config(spec: dict, cell: dict, root: str = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == cell["config"]:
            return load_json(root, c["file"])
    raise SystemExit(f"no config {cell['config']!r} in BENCHMARK.json")


def load_traffic(cell: dict, here: str = HERE) -> dict:
    return load_json(here, "traffic", cell["traffic"] + ".json")


def load_metric(name: str, here: str = HERE) -> dict:
    return load_json(here, "metrics", name + ".json")


def load_cell(workload: str, root: str = ROOT):
    """(spec, cell, configuration, traffic) of one workload, each found
    by the name BENCHMARK.json gives it."""
    spec = load_spec(root)
    cell = find_cell(spec, workload)
    return (spec, cell, load_config(spec, cell, root),
            load_traffic(cell, os.path.join(root, "benchmark")))


def cell_metrics(spec: dict, cell: dict, group: str) -> list:
    """The metrics of ``group`` this cell reports: those that list it
    under ``workloads``, and those that list nothing."""
    return [m for m in spec[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_metric(name: str, obs: dict, here: str = HERE):
    """The metric file names its reader; the reader returns a number or
    None when it finds nothing to read."""
    mf = load_metric(name, here)
    reader = importlib.import_module("benchmark.readers." + mf["reader"])
    return reader.read(obs, **mf.get("args", {}))


# -- what a runner is handed ----------------------------------------------------
class Context:
    def __init__(self, cell, config, traffic, seed, seconds, trace,
                 chips, on_chip=True, control=False):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.chips = int(chips)
        self.on_chip = on_chip
        self.control = bool(control)
        self.setup_s = None
        self.trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.traced = False

    def window_opens(self):
        self.setup_s = time.time() - T_START

    def trace_start(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def trace_stop(self):
        import jax
        jax.profiler.stop_trace()
        self.traced = True

    def write_readings(self, readings: dict):
        """Control runs keep every number read, for setting limits."""
        d = os.path.join(ROOT, "chiprun_out")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(
                d, f"readings_{self.cell['name']}_{self.seed}.json"), "w") as f:
            json.dump(readings, f)

    def free(self):
        """Called by a runner once it has dropped the program's state,
        before the reference runs."""
        import gc
        gc.collect()
        if self.on_chip:
            import jax
            jax.clear_caches()

    def memory_peak(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        return int(max(peaks))


def require_chips(chips: int):
    """Refuse to measure anything but the accelerator the cell asks
    for: no result line, exit code 2."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform!r} ({devs[0].device_kind!r}). "
              f"It does not run elsewhere.", file=sys.stderr)
        raise SystemExit(2)


def place_caches():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), small programs
    included, so that only a checkout's first run compiles."""
    import jax
    from deeplearning4j_tpu.compile_cache import place_compile_cache
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, control: bool = False,
             root: str = ROOT):
    """Everything but printing: returns (result object, observations).
    Tests call this with ``require_chip=False`` and a ``root`` that
    holds tiny data files, on the CPU."""
    spec, cell, config, traffic = load_cell(workload, root)
    if require_chip:
        require_chips(cell["chips"])
        place_caches()
    import jax
    from benchmark import flops, trace as trace_mod
    dev = jax.devices()[0]
    ctx = Context(cell, config, traffic, seed, seconds, trace,
                  cell["chips"], on_chip=require_chip, control=control)
    kind = importlib.import_module("benchmark.kinds." + traffic["kind"])
    obs = kind.run(ctx)
    obs.update(config=config, traffic=traffic, chips=ctx.chips,
               setup_s=ctx.setup_s, trace=None,
               peaks=flops.peaks_for(dev.device_kind) if require_chip
               else {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": ctx.chips,
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    result = {}
    if ctx.traced:
        planes = trace_mod.load(
            trace_mod.find_xplane(ctx.trace_dir),
            device_prefix=traffic.get("device_plane_prefix", "/device:TPU:"))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if planes:
            obs["trace"] = planes
            busy, win = trace_mod.busy_and_window(planes)
            device.update(busy_s=busy, window_s=win)
            full = trace_mod.fullest(planes)
            result["breakdown"] = {"device_ops": trace_mod.top_ops(full),
                                   "idle_gaps": trace_mod.idle_gaps(full)}
    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(spec, cell, group):
        v = read_metric(m["name"], obs, os.path.join(root, "benchmark"))
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = obs["checks"]
    correct = all(lim is None or (val == val and val <= lim)
                  for _, val, lim in checks)
    out = {"correct": bool(correct), "attempted": int(obs["attempted"]),
           "failed": int(obs["failed"]), "metrics": metrics,
           "device": device}
    out.update(result)
    if obs.get("roofline_binds"):       # which side of each roofline binds
        out["roofline_binds"] = obs["roofline_binds"]
    out["failures"] = obs.get("failures", {})
    out["window_s"] = obs["window"]["seconds"]
    for key in ("longest_pause_s", "longest_oversleep_s"):
        if key in obs["window"]:
            out[key] = obs["window"][key]
    if ctx.control:
        out["control"] = config["control_dtype"]
    out["compared"] = {name: {"value": val, "limit": lim}
                       for name, val, lim in checks}
    return out, obs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the lower-precision control's readings in the "
                         "program's place: the run has to end correct: false "
                         "(for setting limits; the driver never asks)")
    a = ap.parse_args(argv)
    out, _ = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      control=bool(a.control))
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # server and prefetch threads must not hold us
