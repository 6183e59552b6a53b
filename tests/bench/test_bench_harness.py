"""The harness on the CPU at a tiny size: what a run refuses, what it
finds by name, and that a broken timed path comes out not correct.

The harness's look for a chip is skipped (``require_tpu=False``); all
else is a whole run: weights from the seed, warm-up, an open-loop
window through the cluster front end, the drain, and the comparison
with the plain reference.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 12345            # past 32 bits, as the driver's seeds are


def tiny_root(tmp: Path, *, vocab=256, layers=2, dtype="bfloat16") -> Path:
    """A checkout-like directory whose BENCHMARK.json holds one tiny cell,
    its configuration and mix dropped in as new files."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json")
                     .read_text())
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=layers, vocab_size=vocab,
               torch_dtype=dtype, max_position_embeddings=128)
    cfg["deployment"] = dict(cfg["deployment"], slots_per_decode_pod=4,
                             max_seq=128, page_tokens=8)
    # set as the configurations' limits are, from readings at this size
    # (CPU): the program 0.004-0.021, the float8 control 0.13-0.44
    cfg["correct_limit"] = {"max_logit_gap": 0.1}
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (tmp / "bench/mixes/tiny-open.json").write_text(json.dumps({
        "arrivals": "poisson", "trace_seed": 3, "rate_per_s": 8.0,
        "prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 40},
        "output_tokens": {"dist": "uniform", "lo": 4, "hi": 8}}))
    (tmp / "bench/metrics/test.requests_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.recs))\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                        "file": "bench/configs/tiny.json", "why": "test"}]
    spec["workloads"] = [{"name": "tiny.open", "config": "tiny",
                          "traffic": "tiny-open", "chips": 1,
                          "why": "test"}]
    spec["per_layer"] = [dict(m, workloads=["tiny.open"])
                         for m in spec["per_layer"]]
    spec["per_layer"].append({
        "name": "test.requests_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "tokens_per_s",
        "workloads": ["tiny.open"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root: Path, *, trace=False, control=False, seconds=2.0):
    from bench import harness
    return harness.run(root, "tiny.open", SEED, seconds, trace,
                       time.perf_counter(), require_tpu=False,
                       control=control)


def _no_json_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def _command(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi4-mini.longprompt", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_without_tpu_exits_nonzero_and_prints_no_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert _no_json_line(p.stdout)
    assert "no TPU" in p.stderr


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in spec["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert _no_json_line(p.stdout)


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A configuration, a mix and a per-layer metric added as files only;
    the traced run reports the new metric and comes out correct."""
    root = tiny_root(tmp_path)
    result, tail, _ = run_tiny(root, trace=True)
    assert result["correct"], tail
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["test.requests_seen"]["value"] == \
        result["attempted"]
    assert "decode.step_ms" in result["metrics"]
    assert list(result)[-1] == "checks"
    # no device plane on the CPU: device metrics are left out, not 0
    assert "device.idle_share" not in result["metrics"]


def test_end_to_end_metrics_of_an_untraced_run(tmp_path):
    root = tiny_root(tmp_path)
    result, _, _ = run_tiny(root)
    m = result["metrics"]
    assert set(m) == {"tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert result["correct"]


def test_token_altered_where_produced_is_not_correct(tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        n = step(self)
        for rid in [s for s in self.slots if s is not None][:1]:
            toks = self.requests[rid].out_tokens
            toks[-1] = (toks[-1] + 1) % self.cfg.vocab_size
        return n
    monkeypatch.setattr(ServeEngine, "step", altered)
    result, tail, _ = run_tiny(tiny_root(tmp_path))
    assert not result["correct"], tail
    assert result["checks"]["max_logit_gap"]["value"] > \
        result["checks"]["max_logit_gap"]["limit"]


def test_pages_lost_in_migration_are_not_correct(tmp_path, monkeypatch):
    """The migration lands zeros in place of the prefill's KV pages."""
    from repro.kernels.wr_scatter import ops
    gather = ops.gather_records

    def zeroed(region, offs, length):
        return gather(region, offs, length) * 0
    monkeypatch.setattr(ops, "gather_records", zeroed)
    result, tail, _ = run_tiny(tiny_root(tmp_path))
    assert not result["correct"], tail


def test_control_reads_far_above_the_program(tmp_path):
    """The control (the reference in float8 in the program's place) on
    the served tokens of a tiny bf16 run reads at least three times the
    program's widest gap, and the harness's own limits judge it not
    correct while the program is."""
    root = tiny_root(tmp_path, vocab=4096, layers=4)
    result, tail, read = run_tiny(root, control=True, seconds=3.0)
    prog, ctl = read["max_logit_gap"], read["control_max_logit_gap"]
    limit = result["checks"]["max_logit_gap"]["limit"]
    assert result["correct"] and prog <= limit
    assert ctl > 3 * max(prog, 0.05), (prog, ctl)
    assert ctl > limit and read["control_correct"] is False, (ctl, limit)
    assert f"check control max_logit_gap: {ctl!r} (limit {limit!r})" in tail
