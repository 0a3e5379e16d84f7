"""Readings that set a cell's limits: the program and its control, per seed.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed one set-up serves two short windows at the cell's own size
and load: the program as the benchmark runs it, then the program with the
control's path switched on (the ``control`` options of the cell's own
file, ``bench/cells/<cell>.json``: a lower message precision).  Each
window's answers are checked as a run checks them.  One JSON line per
seed.  The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run writes
# only inside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seed: int, seconds: float, *, require_tpu=True) -> dict:
    from bench import harness
    harness.devices_for(cell.chips, require_tpu)
    clock = harness.Clock()
    wl = cell.kind.Workload(cell, seed)
    wl.build(clock)
    out = {"seed": seed, "spans": clock.spans, "counters": wl.counters}
    for side, options in (("program", {}), ("control", cell.control)):
        if options:
            wl.set_options(options)
        wl.call(wl.next_request())          # warm: compiles this path
        wl.kept = []
        lat, secs = harness.window(wl, seconds)
        compared = wl.check(cell.limits)
        out[side] = {k: v for k, (v, _) in compared.items()}
        out[side + "_requests"] = len(lat)
        out[side + "_checked"] = wl.checked
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness
    harness.enable_compile_cache(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
