"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
then ``compared``: each number the check compared, beside its limit.  The
same numbers are the last lines of standard error.  Exit codes: 0 after a
result, 2 for a cell the benchmark does not define, 3 when JAX finds no
TPU or fewer chips than the cell asks for.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro  # noqa: F401  (the system under test must be present)
    from bench import harness
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except harness.SpecError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), root=ROOT, t_start=T_START,
                               compiles=harness.CompileCounter())
    except harness.NoDevice as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
