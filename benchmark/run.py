"""python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads BENCHMARK.json, finds the cell's configuration, traffic and metric
readers by name, refuses anything but the TPU the cell asks for, warms the
cell's shapes, measures, checks the outputs against the plain reference
outside the window, and prints the contract's one JSON line last."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from the start of the process

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import sys


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    counters: dict
    reduced: dict | None
    device: dict
    numbers: dict


class ListSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


class Env:
    """What a driver gets from the harness: the clock's origin, the device
    gate, the compile counter, the tracer and the trace reduction."""

    Result = Result
    ListSink = ListSink
    first_steps = 3
    traced_launches = 12

    def __init__(self, root, *, require_chip=True, break_path=None,
                 serve_dtype=None, control_modes=(), first_steps_only=False):
        self.t0 = T0
        self.root = root
        self.cache_dir = os.path.join(root, ".bench_cache")
        self.require_chip = require_chip
        self.break_path = break_path    # tests: break the timed path
        self.serve_dtype = serve_dtype  # calibration: the program's own int8 path
        self.control_modes = tuple(control_modes)  # calibration: reference modes
        self.first_steps_only = first_steps_only   # calibration: no window (train)
        self.control_numbers = {}
        self.setup_s = None
        self.peaks = None
        self.compiles = None

    def open_devices(self, chips):
        import jax

        from benchmark.harness import compiles, device

        if self.require_chip:
            # a fixed path inside the checkout, whatever the machine's
            # JAX_COMPILATION_CACHE_DIR says: the benchmark writes only inside
            # its checkout, so that two checkouts under comparison share
            # nothing, and the path is part of the cache key.  JAX does not
            # make the directory, and without it caches nothing, in silence
            # (my chip runs, PR 23: 7 runs of 480 s)
            jax_cache = os.path.join(self.cache_dir, "jax")
            os.makedirs(jax_cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", jax_cache)
            # no size cap: where the machine caps the cache (192 MiB there), the
            # 23 programs of train-sha-varres evict each other in turn and
            # every run compiles cold (my chip runs, PR 23)
            jax.config.update("jax_compilation_cache_max_size", -1)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            devices, self.peaks = device.require_chips(chips)
        else:
            devices = jax.devices()
        self.compiles = compiles.CompileCounter()
        return devices[:chips] if self.require_chip else devices, self.peaks

    def device_report(self, devices):
        from benchmark.harness import device

        return device.device_report(devices)

    def setup_done(self, t):
        self.setup_s = t - self.t0

    def start_trace(self):
        import jax

        tdir = os.path.join(self.cache_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # the device alone: the host tracer records ~225,000 "Transpose"
        # events per float32 batch and slows the host it measures
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        return tdir

    def stop_trace(self):
        import jax

        jax.profiler.stop_trace()

    def reduce_trace(self, tdir, launches, *, spans, anchor, **kw):
        from benchmark.harness import trace

        events = trace.load(trace.find_xplane(tdir))
        trace.place_spans(events, spans, anchor, kw["program_prefix"])
        shutil.rmtree(tdir, ignore_errors=True)
        return trace.reduce(events, launches, peaks=self.peaks, **kw)


def run_cell(name, seed, seconds, trace, *, root=None, require_chip=True,
             spec_path=None, data_dir=None, break_path=None, serve_dtype=None,
             control_modes=(), first_steps_only=False):
    """One run of one cell; returns the result line as a dict."""
    from benchmark.harness import spec

    root = root or spec.ROOT
    cell = spec.load_cell(name, spec_path=spec_path, data_dir=data_dir)
    readers = ({m["name"]: spec.load_metric_reader(m["name"]) for m in cell.per_layer}
               if trace else {})
    driver = importlib.import_module("benchmark.harness.drive_" + cell.config["driver"])
    env = Env(root, require_chip=require_chip, break_path=break_path,
              serve_dtype=serve_dtype, control_modes=control_modes,
              first_steps_only=first_steps_only)
    res = driver.run(cell, seed, seconds, trace, env)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = {"cell": cell, "counters": res.counters, "trace": res.reduced or {},
               "end_to_end": res.end_to_end}
        values = {n: read(ctx) for n, read in readers.items()}
        values = {n: v for n, v in values.items() if v is not None}
    else:
        values = dict(res.end_to_end, setup_s=env.setup_s)
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in values]
        if missing and not first_steps_only:
            raise RuntimeError(f"cell {name} did not report {missing}")
    line = {"correct": bool(res.correct), "attempted": int(res.attempted),
            "failed": int(res.failed),
            "metrics": {n: {"value": float(v), "unit": units[n]}
                        for n, v in values.items()},
            "device": dict(res.device)}
    if trace and res.reduced:
        line["device"]["busy_s"] = res.reduced["busy_s"]
        line["device"]["window_s"] = res.reduced["window_s"]
        line["breakdown"] = {"device_ops": res.reduced["device_ops"],
                             "idle_gaps": res.reduced["idle_gaps"]}
    line["setup_s"] = env.setup_s
    line["numbers"] = res.numbers
    if env.control_numbers:
        line["control"] = env.control_numbers
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if not os.path.isdir(os.path.join(root, "can_tpu")):
        print("benchmark: no system under test beside BENCHMARK.json "
              f"(no can_tpu/ in {root})", file=sys.stderr)
        return 2
    from benchmark.harness import device, spec, trace

    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        root=root)
    except (spec.SpecError, device.DeviceError, trace.ImpossibleReading) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
