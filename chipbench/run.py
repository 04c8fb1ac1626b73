#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, which holds the chip.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from the profiler's trace),
``device`` and the compared numbers beside their limits (``checks``).
The same numbers end standard error.  A run that finds no TPU, fewer
chips than the cell needs, or a device kind missing from ``peaks.json``
exits with code 2 and prints no result.

``--control 1`` puts the plain reference, computed in a lower precision
than the configuration states, in the program's place: its ``correct``
must come out false.  ``--rate`` overrides a serve cell's offered load
(the knee sweep).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chipbench: the system under test (src/repro) is not in this "
              "checkout; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t0=T0, control=bool(args.control), rate=args.rate)
    except harness.NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
