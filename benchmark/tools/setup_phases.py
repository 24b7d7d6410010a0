"""A cell's ``setup_s`` by phase, and nothing after it: the run ends where the
window would start (no window, no reference), so that a cell's set-up can be
read several times a call on two checkouts.  ``open_devices`` (the chip
coming up: the phase that swings from run to run on unchanged code, PERF.md
section 7) is timed apart; the driver's own ``[setup]`` lines give the
weights and the warm-up.  Touches no file of the harness: it wraps
``run.Env.open_devices`` and ends the process in ``run.Env.setup_done``.

    python3 -m benchmark.tools.setup_phases --workload serve-glm-agent16k-closed --seed 7
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    args = p.parse_args(argv)
    from benchmark import run

    imported = time.perf_counter() - run.T0
    phases = {}
    open_devices = run.Env.open_devices

    def timed_open(self, chips):
        t0 = time.perf_counter()
        phases["before_open_devices_s"] = t0 - run.T0
        out = open_devices(self, chips)
        phases["open_devices_s"] = time.perf_counter() - t0
        return out

    def done(self, t):
        setup_s = t - self.t0
        print(json.dumps({
            "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
            "import_benchmark_run_s": imported, **phases,
            "without_open_devices_s": setup_s - phases.get("open_devices_s", 0.0)}),
            flush=True)
        os._exit(0)

    run.Env.open_devices = timed_open
    run.Env.setup_done = done
    run.run_cell(args.workload, args.seed, 1.0, False)
    return 1      # a driver that never called setup_done


if __name__ == "__main__":
    sys.exit(main())
