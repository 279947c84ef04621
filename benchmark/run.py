"""The benchmark's command.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell. Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for, or when the program
is not in the checkout. The last line of stdout is the result object;
everything else (sample counts, medians, lateness, counters, each number
compared beside its limit) is on the lines before it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                    # harness, families
sys.path.insert(0, os.path.dirname(HERE))   # the program under test


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, platform: str = "tpu") -> int:
    args = parse(argv)
    from harness import modes
    from harness.common import enable_compile_cache, log, require_devices
    from harness.manifest import Cell, load_manifest
    manifest = load_manifest()
    cell = Cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    devs = require_devices(cell.chips, platform)
    import jax
    cache = enable_compile_cache()
    log(f"[device] platform={devs[0].platform} kind={devs[0].device_kind!r}"
        f" count={len(devs)} jax={jax.__version__} cache={cache}")
    log(f"[cell] {cell.name}: config {cell.entry['config']} traffic "
        f"{cell.entry['traffic']} mode {cell.mode} chips {cell.chips} "
        f"seed {args.seed} seconds {args.seconds} trace {args.trace}")
    line = modes.MODES[cell.mode](cell, args, devs, T_PROCESS)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
