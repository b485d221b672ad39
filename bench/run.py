"""Run one cell of BENCHMARK.json on the chip and print its result line.

  python3 bench/run.py --workload mine.t10i4d100k --seed 7 --seconds 51 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. ``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1``
traces part of the window and reports its per-layer metrics. The last line of
standard output is one JSON object; the numbers compared with the plain
reference are the last lines of standard error. With no accelerator, or too
few chips, the run exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from bench import cells  # noqa: E402

CACHE_DIR = os.path.join(cells.ROOT, ".bench_cache", "jax")


def configure_jax():
    """JAX, with its persistent compilation cache at a fixed path in the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load(args.workload)
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    from bench import harness, roofline

    peak = roofline.peaks(devices[0].device_kind)
    devices = devices[: cell.chips]
    harness.log(f"{cell.name}: {len(devices)} x {devices[0].device_kind}, jax {jax.__version__}, "
                f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START, peak)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
