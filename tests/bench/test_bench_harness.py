"""The harness on the CPU at a tiny size: what a run refuses, what it
finds by name, and that a broken timed path comes out not correct.

The harness's look for a chip is skipped (``require_tpu=False``); all
else is a whole run: weights from the seed, warm-up, an open-loop
window through the cluster front end, the drain, and the comparison
with the plain reference.
"""
import dataclasses
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


# -- a configuration whose block is not GQA, added as files only ----------

# the program's own float32 forward: a stand-in for the plain reference
# that checks the harness's plumbing, not the model
_F32_REFERENCE = '''
import dataclasses
import jax
import jax.numpy as jnp
from bench import harness
from repro.models.registry import build_model


def logits_at(weights, cfg, tokens, rows, quant=None):
    model = build_model(dataclasses.replace(harness.program_config(cfg),
                                            dtype="float32"))
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    logits, _ = model.forward(w, jnp.asarray(tokens))
    return logits.reshape(-1, logits.shape[-1])[jnp.asarray(rows)]
'''

# DeepSeek-V3's leaves beyond RULES, in the file's own form
MLA_MOE_RULES = {
    "mla/w_dq": ["normal", [-2]], "mla/w_dkv": ["normal", [-2]],
    "mla/w_kr": ["normal", [-2]], "mla/w_uq": ["normal", [-3]],
    "mla/w_uk": ["normal", [-3]], "mla/w_uv": ["normal", [-3]],
    "mla/w_o": ["normal", [-3, -2]], "q_norm/scale": ["ones", None],
    "kv_norm/scale": ["ones", None], "router/w": ["normal", [-2]],
    "router/bias": ["zeros", None], "experts/gate": ["normal", [-2]],
    "experts/up": ["normal", [-2]], "experts/down": ["normal", [-2]],
    "mtp/proj": ["normal", [-2]], "norm_h/scale": ["ones", None],
    "norm_e/scale": ["ones", None]}


def mla_moe_config() -> dict:
    """A tiny DeepSeek-V3-like file: MLA, one dense layer then two MoE
    layers of 8 routed experts (top 2) and one shared, one MTP block;
    published key names, no `head_dim`, float32."""
    cfg = json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json")
                     .read_text())
    del cfg["head_dim"]
    cfg.update(
        name="tiny-mla-moe", program_arch="deepseek-v3-671b",
        reference="test_program_f32", hidden_size=64,
        num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=3,
        vocab_size=256, rms_norm_eps=1e-6, tie_word_embeddings=False,
        max_position_embeddings=128, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, first_k_dense_replace=1, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_nextn_predict_layers=1,
        program_fields={"moe.capacity_factor": 1.5},
        weight_rules=MLA_MOE_RULES)
    cfg["deployment"] = dict(cfg["deployment"], slots_per_decode_pod=4,
                             max_seq=128, page_tokens=8)
    # served in float32, the stand-in's own precision, the served tokens
    # are its argmax: 0.0 on eight seeds (CPU). Served in bfloat16 the
    # same cell reads 0.0-0.70, its routing flipping on rounding.
    cfg.update(torch_dtype="float32", correct_limit={"max_logit_gap": 0.01})
    return cfg


def mla_moe_root(tmp: Path) -> Path:
    """`tiny_root` with its configuration replaced by `mla_moe_config`
    and the stand-in reference; per-layer metrics that read the GQA work
    counts (`ctx.shapes`) are left out of the cell, as a later cell of
    such a block would leave them."""
    root = tiny_root(tmp)
    (root / "bench/configs/tiny.json").write_text(
        json.dumps(mla_moe_config()))
    (root / "bench/references/test_program_f32.py").write_text(
        _F32_REFERENCE)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    gqa = {"prefill.mfu", "migrate.gb_per_s", "decode.mfu",
           "kernel.decode_hbm_roofline", "kernel.kv_landing_roofline"}
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] not in gqa]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_mla_moe_config_added_as_files_runs_correct(tmp_path, trace):
    """Sparse experts, latent attention and MTP stated by the file alone
    reach the program, and a whole run through the cluster is correct."""
    from bench import harness
    root = mla_moe_root(tmp_path)
    mc = harness.program_config(
        json.loads((root / "bench/configs/tiny.json").read_text()))
    assert (mc.moe.n_experts, mc.moe.top_k, mc.moe.n_shared,
            mc.moe.first_dense) == (8, 2, 1, 1)
    assert (mc.d_ff, mc.moe.d_ff_expert, mc.moe.d_ff_shared,
            mc.moe.d_ff_dense) == (32, 32, 32, 128)
    assert (mc.mla.q_lora_rank, mc.mla.kv_lora_rank, mc.mla.qk_nope_head_dim,
            mc.mla.qk_rope_head_dim, mc.mla.v_head_dim) == (32, 32, 16, 8, 16)
    assert (mc.mtp_depth, mc.n_layers, mc.moe.capacity_factor) == (1, 3, 1.5)
    result, tail, _ = run_tiny(root, trace=trace)
    assert result["correct"], tail
    assert result["failed"] == 0 and result["attempted"] > 0
    # an MoE block's prefill is not bucketed and lowers inside the window,
    # so what the window holds besides depends on the CPU's load: nothing
    # timed is read here
    if trace:
        assert result["metrics"]["test.requests_seen"]["value"] == \
            result["attempted"]
    else:
        assert np.isfinite(result["metrics"]["ttft_p90_ms"]["value"])


def test_mla_moe_token_altered_where_produced_is_not_correct(
        tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        n = step(self)
        for rid in [s for s in self.slots if s is not None][:1]:
            toks = self.requests[rid].out_tokens
            toks[-1] = (toks[-1] + 1) % self.cfg.vocab_size
        return n
    monkeypatch.setattr(ServeEngine, "step", altered)
    result, tail, _ = run_tiny(mla_moe_root(tmp_path))
    assert not result["correct"], tail


def _rule_error(cfg):
    from bench import harness, weights
    weights.make(harness.program_model(cfg), SEED, cfg)


def _field_error(cfg):
    from bench import harness
    harness.program_config(cfg)


def _phi4():
    return json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json")
                      .read_text())


def _without(cfg, *keys):
    return {k: v for k, v in cfg.items() if k not in keys}


@pytest.mark.parametrize("make_cfg, fails, match", [
    (lambda: _without(mla_moe_config(), "weight_rules"), _rule_error,
     "no weight rule for leaf groups/b0/mla/kv_norm/scale"),
    (lambda: dict(mla_moe_config(), weight_rules=dict(
        MLA_MOE_RULES, **{"ln1/scale": ["ones", None]})), _rule_error,
     "'ln1/scale' in the configuration file redefines"),
    (lambda: dict(mla_moe_config(), weight_rules=dict(
        MLA_MOE_RULES, **{"lm/gate": ["normal", [-2]]})), _rule_error,
     r"weight rules no leaf uses: \['lm/gate'\]"),
    (lambda: dict(mla_moe_config(), program_fields={"moe.n_expert": 8}),
     _field_error, "no ModelConfig field 'moe.n_expert'"),
    (lambda: dict(_phi4(), n_routed_experts=8), _field_error,
     r"disagree on the block: phi4-mini-3.8b lacks moe.*n_routed_experts"),
    (lambda: _without(mla_moe_config(), "q_lora_rank", "kv_lora_rank",
                      "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"),
     _field_error, "disagree on the block: deepseek-v3-671b has mla"),
], ids=["unknown-leaf", "redefined-rule", "unused-rule",
        "unknown-field", "moe-keys-on-dense", "mla-unstated"])
def test_config_file_errors_name_their_cause(make_cfg, fails, match):
    with pytest.raises((KeyError, ValueError), match=match):
        fails(make_cfg())


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "internvl2-2b"])
def test_shipped_configs_resolve_as_from_the_gqa_fields_alone(name):
    """The shipped files build the ModelConfig that `_FIELDS` alone gives,
    and every leaf of their tree takes its rule from `RULES`: the same
    model and the same weights as before files could state more."""
    import jax
    from bench import harness, weights
    from repro.configs.base import FrontendConfig, get_config
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    base = get_config(cfg["program_arch"])
    want = dataclasses.replace(base, frontend=FrontendConfig(), **{
        f: type(getattr(base, f))(cfg[k])
        for f, k in harness._FIELDS.items()})
    assert harness.program_config(cfg) == want
    model = harness.program_model(cfg)
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert weights.rules(leaves, cfg) == [
        weights.RULES[weights._names(p)[-2:]] for p, _ in leaves]
