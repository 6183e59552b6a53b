#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it is
started on:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (configuration, traffic mix, metrics) is looked up by name in
``BENCHMARK.json``. Set-up builds the cluster, makes the weights from
the seed and warms every shape the traffic uses; then the traffic runs
for ``--seconds``; then the served tokens are checked against the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window.

Diagnostics go to stderr and to earlier lines of stdout; the last line
of stdout is one JSON object. Without a TPU, with another number of
chips than the cell asks for, or on any error the command exits
non-zero and prints no result. JAX's compilation cache is kept in
``.jax_compile_cache/`` of the checkout, so only a checkout's first run
of a cell compiles.
"""
import time

T0 = time.perf_counter()

import argparse                                     # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import sys                                          # noqa: E402
import traceback                                    # noqa: E402
from pathlib import Path                            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_compile_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX, and the program, take the cache directory from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        from bench import harness
        result, tail, _ = harness.run(ROOT, args.workload, args.seed,
                                   args.seconds, bool(args.trace), T0)
    except Exception as e:                  # no result on any failure
        if type(e).__name__ != "NoChip":
            traceback.print_exc()
        print(f"[bench] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    for line in tail:
        print(f"[bench] {line}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
