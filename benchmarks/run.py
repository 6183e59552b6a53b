"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig15] [--json-dir .]

Prints ``name,us_per_call,derived`` CSV (the brief's contract) and writes
one ``BENCH_<name>.json`` per module (metrics + parsed counters) so the
perf trajectory is tracked in-repo from PR 3 on — see scripts/bench.sh.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

MODULES = [
    "benchmarks.bench_transfer",       # Fig 10 + 11
    "benchmarks.bench_tx_path",        # Fig 12 + 13
    "benchmarks.bench_rx_path",        # Fig 14
    "benchmarks.bench_notification",   # Fig 15
    "benchmarks.bench_offload",        # Fig 16
    "benchmarks.bench_solar",          # Fig 17
    "benchmarks.bench_kvtransfer",     # Fig 18
    "benchmarks.bench_verbs",          # §4 verbs-layer overhead
    "benchmarks.bench_srq",            # SRQ / doorbell batching / CQ credit
    "benchmarks.bench_line_rate",      # ISSUE 3: batch-wise dispatch chains
    "benchmarks.bench_fabric",         # ISSUE 5: routed multi-pod fabric
    "benchmarks.bench_moe_dispatch",   # Table 1 / §5.3 training-plane
    "benchmarks.bench_fault",          # ISSUE 8: unreliable fabric
    "benchmarks.bench_serve_cluster",  # ISSUE 10: disaggregated serving
]


def _parse_derived(derived: str) -> dict:
    """'a=1;b=2.5x;c=foo' -> {'a': 1.0, 'b': 2.5, 'c': 'foo'} (numbers
    parsed where possible, trailing 'x' multipliers included)."""
    out: dict = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v[:-1] if v.endswith("x") else v)
        except ValueError:
            out[k] = v
    return out


def _write_json(json_dir: str, modname: str, rows, registry) -> str:
    short = modname.rsplit(".", 1)[-1].removeprefix("bench_")
    path = os.path.join(json_dir, f"BENCH_{short}.json")
    out_rows = []
    bench_scope = registry.scope("bench")
    for name, us, derived in rows:
        row = {"name": name, "us_per_call": round(float(us), 3),
               "derived": _parse_derived(derived),
               "derived_raw": str(derived)}
        if hasattr(us, "p95"):
            # TimingStats: tail latency rides the row AND the registry
            # (as a per-row histogram, unless a time_call label already
            # recorded these samples under this name)
            row["us_p95"] = round(float(us.p95), 3)
            row["us_max"] = round(float(us.max), 3)
            if name not in bench_scope.metrics and \
                    hasattr(us, "samples"):
                bench_scope.histogram(name).observe_many(us.samples)
        out_rows.append(row)
    payload = {
        "benchmark": short,
        "rows": out_rows,
        # instance-collapsed registry snapshot of THIS module's run:
        # every counter the datapath touched, benchmark-agnostic
        "metrics": registry.aggregate(),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="")
    p.add_argument("--json-dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="where BENCH_<name>.json land (default: repo root); "
             "'' disables JSON output")
    args = p.parse_args()

    import importlib

    from repro import compile_cache
    from repro.obs import metrics

    compile_cache.enable()

    print("name,us_per_call,derived")
    failed = []
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        try:
            # one empty registry per module: the JSON "metrics" block
            # covers exactly this module's run, nothing carried over
            registry = metrics.fresh_registry()
            mod = importlib.import_module(modname)
            rows = list(mod.run())
            for name, us, derived in rows:
                print(f"{name},{us:.2f},{derived}")
            sys.stdout.flush()
            if args.json_dir:
                path = _write_json(args.json_dir, modname, rows, registry)
                print(f"# wrote {path}")
        except Exception:
            traceback.print_exc()
            failed.append(modname)
    if failed:
        print(f"# FAILED modules: {failed}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
