#!/usr/bin/env python3
"""Readings a cell's limits are set from, gathered in ONE process on the
chip (never run by the benchmark's own runs): per seed, the program's
and the float8 control's widest logit gap, and each one's verdict
under the configuration's limits.

    python3 bench/calibrate.py --workload <name> --seconds <s> \\
        --seeds 11,12,13

Each line of stdout that starts with ``{`` is one JSON reading.
"""
import time

T0 = time.perf_counter()

import argparse                                     # noqa: E402
import gc                                           # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_compile_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    for s in [int(x) for x in args.seeds.split(",")]:
        t0 = time.perf_counter()
        result, _, read = harness.run(ROOT, args.workload, s, args.seconds,
                                      False, t0, control=True)
        print(json.dumps({"seed": s, "readings": read,
                          "correct": result["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      result["metrics"].items()},
                          "memory_peak_bytes":
                              result["device"]["memory_peak_bytes"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
