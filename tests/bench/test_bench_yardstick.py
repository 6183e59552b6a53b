"""The yardstick's own arithmetic on the CPU: required work from shapes,
the table of peaks, and the traffic generator."""
import numpy as np
import pytest

from bench import peaks, traffic, work

TINY = {"num_hidden_layers": 2, "hidden_size": 4, "intermediate_size": 8,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
        "vocab_size": 10, "tie_word_embeddings": True,
        "torch_dtype": "bfloat16", "deployment": {"page_tokens": 2}}


def test_required_work_by_hand():
    s = work.Shapes(TINY)
    # per layer: q 4*2*2, k and v 2*4*1*2, o 2*2*4, SwiGLU 3*4*8
    assert s.layer_params == 2 * (16 + 16 + 16 + 96)
    assert s.head_params == 40
    # 2 flops per weight per token, causal QK^T and PV over 1+2+3
    # positions in 2 layers of 2 heads of width 2, the head once
    assert s.prefill_flops(3) == 2 * 288 * 3 + 4 * 2 * 2 * 2 * 6 + 2 * 40
    # two decode tokens attending over 3 and 5 tokens
    assert s.decode_flops([3, 5]) == 2 * 2 * 328 + 4 * 2 * 2 * 2 * 8
    # K and V, 2 layers, 1 kv head of width 2, 2 bytes
    assert s.kv_bytes_per_token == 16
    assert s.page_record_bytes == 2 * 2 * 1 * 2 * 2
    assert s.migrated_bytes(3) == 2 * 2 * 16      # 2 pages, K and V
    assert s.decode_weight_bytes(2) == 328 * 2    # tied: head reads table
    assert work.Shapes(dict(TINY, tie_word_embeddings=False)) \
        .decode_weight_bytes(2) == (328 + 2 * 4) * 2
    assert s.decode_kv_bytes([3, 5]) == 8 * 16


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


OPEN = {"arrivals": "poisson", "trace_seed": 11, "rate_per_s": 3.0,
        "prompt_tokens": {"dist": "loguniform", "lo": 256, "hi": 960},
        "output_tokens": {"dist": "uniform", "lo": 16, "hi": 32}}
CLOSED = {"arrivals": "closed", "trace_seed": 11, "clients": 4, "rounds": 5,
          "prompt_tokens": {"dist": "uniform", "lo": 32, "hi": 128},
          "output_tokens": {"dist": "uniform", "lo": 64, "hi": 192}}


def test_every_seed_gets_the_same_schedule_and_other_tokens():
    a = traffic.generate(OPEN, vocab=100, seed=1, seconds=16)
    b = traffic.generate(OPEN, vocab=100, seed=2**33 + 1, seconds=16)
    pa = [r.prompt.size for r in a.requests]
    assert len(pa) == 48 and pa == [r.prompt.size for r in b.requests]
    assert [(r.max_new, r.at) for r in a.requests] == \
        [(r.max_new, r.at) for r in b.requests]
    assert not all((x.prompt == y.prompt).all()
                   for x, y in zip(a.requests, b.requests))
    at = [r.at for r in a.requests]
    assert at == sorted(at) and 0 <= at[0] and at[-1] < 16
    assert min(pa) >= 256 and max(pa) <= 960
    # another trace seed is another realisation of the same mix
    c = traffic.generate(dict(OPEN, trace_seed=12), vocab=100, seed=1,
                         seconds=16)
    assert len(c.requests) == 48 and [r.at for r in c.requests] != at


def test_open_loop_trace_is_an_unsmoothed_poisson_draw():
    """Arrival times uniform over the window given their count (a Poisson
    process conditioned on it), lengths independent draws: over a long
    trace the gaps are exponential and the lengths fill their ranges."""
    t = traffic.generate(dict(OPEN, rate_per_s=2.0), vocab=10, seed=1,
                         seconds=2000)
    at = np.array([r.at for r in t.requests])
    gaps = np.diff(at)
    assert len(at) == 4000
    assert abs(gaps.mean() - 0.5) < 0.03
    assert abs(np.median(gaps) - 0.5 * np.log(2)) < 0.03     # exponential
    assert gaps.std() / gaps.mean() > 0.9        # not smoothed: CV about 1
    pl = np.array([r.prompt.size for r in t.requests])
    assert pl.min() == 256 and pl.max() == 960
    # log-uniform: half the prompts under the geometric mean
    assert abs((pl < np.sqrt(256 * 961)).mean() - 0.5) < 0.03
    out = [r.max_new for r in t.requests]
    assert min(out) == 16 and max(out) == 32


def test_closed_loop_rounds_hold_the_same_sizes():
    """Every run replays the same rounds: each round holds one request per
    client, and the sizes follow the trace, not the run's seed."""
    t = traffic.generate(CLOSED, vocab=100, seed=7, seconds=10)
    u = traffic.generate(CLOSED, vocab=100, seed=8, seconds=10)
    sizes = [[(r.prompt.size, r.max_new) for r in rnd] for rnd in t.rounds]
    assert sizes == [[(r.prompt.size, r.max_new) for r in rnd]
                     for rnd in u.rounds]
    assert len(t.rounds) == 5 and len(set(map(tuple, sizes))) > 1
    assert all([r.client for r in rnd] == [0, 1, 2, 3] for rnd in t.rounds)


def test_same_seed_same_requests():
    a = traffic.generate(OPEN, vocab=100, seed=5, seconds=8)
    b = traffic.generate(OPEN, vocab=100, seed=5, seconds=8)
    assert all((x.prompt == y.prompt).all() and x.at == y.at
               for x, y in zip(a.requests, b.requests))
