"""One run of one cell: build, warm, measure, check, report.

    result = harness.run(root, "phi4-mini.longprompt", seed, seconds,
                         trace=False, t0=process_start)

`run` returns the result object the command prints as its last line,
the lines that name each compared number beside its limit, and every
reading of the comparison (`check.readings`). With `control`, the
float8 control is judged by the same limits in the program's place and
its verdict is the reading ``control_correct``. It
raises `NoChip` when JAX finds no TPU, or another number of chips than
the cell asks for: there is no fallback to the CPU.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from bench import cells, check, device_trace, peaks, session, traffic, \
    weights, work


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chips(chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) != chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


# the program's ModelConfig fields, from the configuration file's keys
_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
           "d_ff": "intermediate_size", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "tie_embeddings": "tie_word_embeddings", "dtype": "torch_dtype"}
# more of them, by dotted path, for each part a registry entry may have
# beyond a GQA block: sparse experts, latent attention, multi-token
# prediction. A file states keys of a part exactly when its entry has it.
_PARTS = {
    "moe": {"moe.n_experts": "n_routed_experts",
            "moe.top_k": "num_experts_per_tok",
            "moe.d_ff_expert": "moe_intermediate_size",
            "moe.d_ff_shared": "moe_intermediate_size",
            "d_ff": "moe_intermediate_size",    # the program's expert width
            "moe.n_shared": "n_shared_experts",
            "moe.first_dense": "first_k_dense_replace",
            "moe.d_ff_dense": "intermediate_size"},  # the dense layers'
    "mla": {f"mla.{k}": k for k in ("q_lora_rank", "kv_lora_rank",
                                    "qk_nope_head_dim", "qk_rope_head_dim",
                                    "v_head_dim")},
    "mtp_depth": {"mtp_depth": "num_nextn_predict_layers"},
}


def _replace(obj, path: str, value):
    """`obj` with the scalar field at dotted `path` set to `value`, in
    the field's own type."""
    names = path.split(".")
    chain = [obj]
    for n in names:
        o = chain[-1]
        if not dataclasses.is_dataclass(o) or n not in {
                f.name for f in dataclasses.fields(o)}:
            raise KeyError(f"no ModelConfig field {path!r}")
        chain.append(getattr(o, n))
    old = chain.pop()
    if not isinstance(old, (bool, int, float, str)):
        raise KeyError(f"ModelConfig field {path!r} is not a size")
    new = type(old)(value)
    if new != value:
        raise ValueError(f"ModelConfig field {path!r}: {value!r} is not a "
                         f"{type(old).__name__}")
    for o, n in zip(reversed(chain), reversed(names)):
        new = dataclasses.replace(o, **{n: new})
    return new


def _get(obj, path: str):
    for name in path.split("."):
        obj = getattr(obj, name)
    return obj


def program_config(cfg: dict):
    """The program's ModelConfig: its registry entry with every size the
    configuration file states, by published key (`_FIELDS`, `_PARTS`)
    and then by dotted field (``program_fields``). A registry value the
    file leaves unstated, differs from or sets by field is logged."""
    from repro.configs.base import FrontendConfig, get_config
    arch, name = cfg["program_arch"], cfg["name"]
    base = get_config(arch)
    fields = dict(_FIELDS)
    for part, table in _PARTS.items():
        stated = sorted((set(table.values()) - set(_FIELDS.values()))
                        & cfg.keys())
        if bool(getattr(base, part)) != bool(stated):
            raise ValueError(
                f"the configuration and the program disagree on the block: "
                f"{arch} {'has' if getattr(base, part) else 'lacks'} "
                f"{part}, {name}'s file states {stated or 'none of its keys'}")
        if stated:
            fields.update(table)
    if base.act != "swiglu" or base.qkv_bias or cfg["hidden_act"] != "silu":
        raise ValueError("the configuration and the program disagree on "
                         "the block")
    # the served model is text-only: no modality frontend
    out = dataclasses.replace(base, frontend=FrontendConfig())
    for f, k in fields.items():
        if k not in cfg:
            log(f"{arch}: {f} not stated in {name}'s file; the registry's "
                f"{_get(base, f)!r} kept")
            continue
        out = _replace(out, f, cfg[k])
        if _get(base, f) != _get(out, f):
            log(f"{arch}: {f} {_get(base, f)!r} in the program's registry, "
                f"{_get(out, f)!r} in {name}'s file")
    for f, v in cfg.get("program_fields", {}).items():
        out = _replace(out, f, v)
        log(f"{arch}: {f} {_get(base, f)!r} in the program's registry, "
            f"{_get(out, f)!r} by {name}'s program_fields")
    return out


def program_model(cfg: dict):
    """The program's model of the configuration file `cfg`."""
    from repro.models.registry import build_model
    return build_model(program_config(cfg))


class DeviceTrace:
    """The profiler's device trace plus the verbs datapath tracer, around
    the measured window only. The trace goes to a temporary directory
    that is removed once reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.tracer = None

    def start(self):
        from repro.obs import trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.tracer = trace.install(trace.Tracer(capacity=1 << 20))

    def stop(self):
        from repro.obs import trace
        trace.uninstall()
        jax.profiler.stop_trace()
        try:
            summary = device_trace.reduce(device_trace.load(
                device_trace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        verbs = [(e[2], e[2] + e[3]) for e in self.tracer.events()
                 if e[0] == "X"]
        return summary, verbs


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""
    cfg: dict
    peaks: dict | None
    window: session.Window
    spans: session.Spans
    trace: device_trace.Summary | None

    @functools.cached_property
    def shapes(self) -> work.Shapes:
        """The GQA block's work counts, built when a reader first asks,
        so a run of another block fails only where a reader needs them."""
        return work.Shapes(self.cfg)

    def spans_in_window(self, name: str) -> list:
        return self.spans.between(name, self.window.start, self.window.end)

    def plen(self) -> dict[int, int]:
        return {r.rid: int(r.req.prompt.size) for r in self.window.recs}


def p90(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 90))


def judge(read: dict, limits: dict, prefix: str = "") -> tuple[dict, bool]:
    """Each compared number, `prefix` + its name in `read`, beside its
    limit; correct when every one is there and within its limit."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = read.get(prefix + name)
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value is not None and value <= limit
    return checks, ok


def end_to_end(win: session.Window, setup_s: float) -> dict:
    deadline = win.end + session.DRAIN_S
    ttft = [((r.t_first if r.t_first is not None else deadline) - r.sched)
            for r in win.recs]
    tpot = [(r.t_done - r.t_first) / (len(r.served) - 1)
            for r in win.recs if r.served and len(r.served) > 1]
    return {"tokens_per_s": win.window_tokens / win.seconds,
            "ttft_p90_ms": p90(ttft) * 1e3 if ttft else math.nan,
            "tpot_p90_ms": p90(tpot) * 1e3 if tpot else math.nan,
            "setup_s": setup_s}


def run(root, workload: str, seed: int, seconds: float, trace: bool,
        t0: float, *, bench_dir=None, require_tpu: bool = True,
        control: bool = False) -> tuple[dict, list[str], dict]:
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "bench"
    cell = cells.load_cell(root, workload, bench_dir)
    devs = require_chips(cell.chips, require_tpu)
    kind = devs[0].device_kind
    peak = peaks.peak(kind) if require_tpu else None
    cfg = cell.config
    clock = session.CompileClock()
    marks = [("start", time.perf_counter())]
    model = program_model(cfg)
    params = weights.make(model, seed, cfg)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    tr = traffic.generate(cell.mix, vocab=int(cfg["vocab_size"]), seed=seed,
                          seconds=seconds)
    sess = session.Session(model, params, cfg, clock)
    marks.append(("cluster", time.perf_counter()))
    n_warm = sess.warm(tr)
    marks.append(("warm-up", time.perf_counter()))
    # what set-up left behind is never scanned by the collector again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    phases = ", ".join(f"{b[0]} {b[1] - a[1]:.3f} s"
                       for a, b in zip(marks, marks[1:]))
    log(f"set-up {setup_s:.3f} s: JAX and the chip "
        f"{marks[0][1] - t0:.3f} s, {phases}; {n_warm} warm-up requests, "
        f"{clock.compile_s:.3f} s lowering and compiling or loading "
        f"({clock.lowerings} lowerings, {clock.compiles} compiles)")
    win = sess.run(tr, seconds, DeviceTrace() if trace else None)
    mem = sess.memory_peak()
    spans = sess.spans
    gc.unfreeze()
    sess.close()
    del sess

    due = len(win.recs)
    failed = sum(1 for r in win.recs if not r.served)
    lat = np.asarray(win.lateness or [0.0])
    lines = [
        f"window {win.seconds:.3f} s: {due} requests due, {failed} "
        f"unfinished after the drain, {win.window_tokens} output tokens",
        f"window compiles: {win.compiles[0]} lowerings, {win.compiles[1]} "
        f"backend compiles or cache loads, {win.compiles[2]:.3f} s",
        f"generator lateness: median {np.median(lat) * 1e3:.3f} ms, max "
        f"{lat.max() * 1e3:.3f} ms",
    ]
    for ln in lines:
        print(f"[bench] {ln}", flush=True)

    ref_mod = check.load_reference(bench_dir, cfg["reference"])
    picked = check.sample(win.recs, seed)
    read = check.readings(params, cfg, picked, ref_mod, control=control) \
        if picked else {}
    limits = cfg.get("correct_limit", {})
    checks, ok = judge(read, limits)
    ok = ok and bool(picked) and failed == 0
    if control:
        # the control in the program's place, through the same limits
        ctl_checks, read["control_correct"] = judge(read, limits,
                                                    "control_")
    if win.compiles[0]:
        log(f"lowered inside the window: {win.compiled_names}")

    if trace:
        ctx = Context(cfg, peak, win, spans, win.trace_summary)
        values = {m.name: cell.readers[m.name](ctx) for m in cell.per_layer}
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.per_layer if values[m.name] is not None}
    else:
        values = end_to_end(win, setup_s)
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": due, "failed": failed,
              "metrics": metrics, "device": device}
    s = win.trace_summary
    if trace and s is not None:
        device["busy_s"] = s.busy_mean_s
        device["window_s"] = s.window_s
        result["breakdown"] = device_trace.breakdown(s)
    result["checks"] = checks
    tail = [f"{k} {v}" for k, v in read.items() if k not in limits]
    if control:
        tail += [f"check control {k}: {c['value']!r} (limit "
                 f"{c['limit']!r})" for k, c in ctl_checks.items()]
    tail += [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
             for k, c in checks.items()]
    return result, tail, read
