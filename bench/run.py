"""Run one cell of the benchmark once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for (``BENCHMARK.json``). It refuses to run without a TPU, or without
the program under ``src/``, and then prints no result. With ``--trace 0`` the
last line of standard output is the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the trace's breakdown; each number
the correctness check compares is printed beside its limit as the last lines
of standard error and under ``checks`` in that line.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# libtpu otherwise writes its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# JAX's persistent compile cache, inside the checkout at a fixed path: only
# a checkout's first run compiles, and nothing is shared outside it
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
(ROOT / ".jax_cache").mkdir(exist_ok=True)     # JAX does not make it
# no size limit, so no eviction: an eviction pass fails on any entry that a
# process without a limit wrote, and then nothing more is cached
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib.spec import SpecError, load_cell

    try:
        cell = load_cell(args.workload)
    except SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program under test is not at {ROOT / 'src'}; "
              "refusing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from benchlib.device import NoChip, chips_for, describe

    try:
        devices = chips_for(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"device: {describe(devices)}", flush=True)

    from benchlib.harness import dumps, print_checks, run_cell

    try:
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START, devices=devices)
    except Exception:  # noqa: BLE001 - a failed run prints no result
        traceback.print_exc()
        return 1
    print_checks(line)
    print(dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
