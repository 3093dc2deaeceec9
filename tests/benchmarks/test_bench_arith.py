"""Percentile, lateness and gap arithmetic; FLOPs and flash ops/bytes against
hand-worked values; the traffic generator's promises; the peaks table."""
import numpy as np
import pytest

from benchmarks.lib import flops, harness, loadgen, peaks, seeds, stats
from benchmarks.lib import traffic as traffic_mod


def config(name):
    """A configuration's sizes; Cerebras-GPT's file waits among the tests'
    fixtures until a cell uses it."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    for path in (os.path.join(harness.BENCH_DIR, "configs", name + ".json"),
                 os.path.join(here, "config_%s.json" % name)):
        if os.path.exists(path):
            return harness.load_json(path)["gpt_config"]
    raise FileNotFoundError(name)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.95, 10.0),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 0.5, 5.0),
    (list(range(1, 101)), 0.95, 95.0),
    ([3.0], 0.95, 3.0),
    ([2, 1], 0.0, 1.0),
])
def test_percentile_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.95)


def test_quartile_spread_is_the_contracts():
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx((q3 - q1) / 100.0)


def rec(due, sent, arrivals, status=200, reason="length", want=None):
    ids = [0] * sum(k for _, k in arrivals)
    return {"due": due, "sent": sent, "status": status, "token_ids": ids,
            "arrivals": arrivals, "finish_reason": reason,
            "max_new_tokens": len(ids) if want is None else want}


def test_ttft_is_from_due_time_and_failures_miss():
    recs = [rec(10.0, 10.5, [(11.0, 1), (11.2, 1)]),
            rec(12.0, 12.0, [], status=429, reason=None)]
    assert loadgen.ttfts(recs, t_give_up=20.0) == [1.0, 8.0]
    assert loadgen.lateness(recs) == [0.5, 0.0]


def test_gaps_count_every_token():
    recs = [rec(0, 0, [(1.0, 1), (1.5, 2), (1.75, 1)])]
    assert loadgen.gaps(recs) == [0.5, 0.0, 0.25]
    assert loadgen.tokens_inside(recs, 0.0, 1.6) == 3


def test_completed_needs_every_token():
    assert loadgen.completed(rec(0, 0, [(1.0, 2)]))
    assert not loadgen.completed(rec(0, 0, [(1.0, 2)], want=3))
    assert not loadgen.completed(rec(0, 0, [(1.0, 2)], reason=None))
    assert not loadgen.completed(rec(0, 0, [], status=503))


def test_model_flops_gpt2_medium_by_hand():
    # 6 x (12 x 24 x 1024^2 + 50304 x 1024) + 6 x 24 x 1024 x 1024
    want = 6 * (12 * 24 * 1024 ** 2 + 50304 * 1024) + 6 * 24 * 1024 * 1024
    assert want == 2_272_002_048
    assert flops.model_flops_per_token(config("gpt2-medium"), 1024) == want


def test_model_flops_cerebras_by_hand():
    # 6 x (12 x 24 x 2048^2 + 50304 x 2048) + 6 x 24 x 2048 x 2048
    want = 6 * (12 * 24 * 2048 ** 2 + 50304 * 2048) + 6 * 24 * 2048 * 2048
    assert want == 8_469_872_640
    assert flops.model_flops_per_token(config("cerebras-gpt-1.3b"),
                                       2048) == want


@pytest.mark.parametrize("name,batch,seq,fwd,bwd", [
    # one matmul: 2 x b x H x s^2 x d / 2 (causal); 2 forward, 5 backward
    ("gpt2-medium", 16, 1024, 2 * 16 * 16 * 1024 ** 2 * 64,
     5 * 16 * 16 * 1024 ** 2 * 64),
    ("cerebras-gpt-1.3b", 4, 2048, 2 * 4 * 16 * 2048 ** 2 * 128,
     5 * 4 * 16 * 2048 ** 2 * 128),
])
def test_flash_flops_by_hand(name, batch, seq, fwd, bwd):
    cfg = config(name)
    heads = cfg["num_attention_heads"]
    got = flops.flash_flops(batch, seq, heads, cfg["hidden_size"] // heads)
    assert got == {"fwd": fwd, "bwd": bwd}


def test_flash_bytes_and_least_time_by_hand():
    # gpt2-medium, 16 x 1024: one tensor is 16 x 1024 x 1024 x 2 B = 32 MiB,
    # the log-sum-exp 16 x 16 x 1024 x 4 B = 1 MiB
    mib = 1 << 20
    assert flops.flash_bytes(16, 1024, 16, 64) == {
        "fwd": 4 * 32 * mib + mib, "bwd": 8 * 32 * mib + mib}
    least = flops.flash_least_seconds(config("gpt2-medium"), 16, 1024,
                                      peaks.peaks("TPU v5 lite"))
    # 24 x 7 x 16 x 16 x 1024^2 x 64 = 2.886e12 FLOP / 197e12 = 14.65 ms
    assert least["compute_seconds"] == pytest.approx(14.65e-3, rel=1e-3)
    # 24 x (12 x 32 + 2) MiB = 9.71e9 B / 819e9 = 11.86 ms
    assert least["bandwidth_seconds"] == pytest.approx(11.86e-3, rel=1e-3)
    assert least["bound"] == "compute"


def test_unknown_part_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


CHAT = {"kind": "serve_open", "rate_rps": 5.0, "max_total": 2048,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                       "min": 64, "max": 1536},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                       "min": 16, "max": 384}}


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic_mod.open_plan(CHAT, 1, 40.0, 50257)
    b = traffic_mod.open_plan(CHAT, 2 ** 31 + 252, 40.0, 50257)
    assert len(a) == len(b) == 200
    lens = lambda plan: sorted(len(p["prompt"]) for _, p in plan)
    outs = lambda plan: sorted(p["max_new_tokens"] for _, p in plan)
    gaps = lambda plan: sorted(np.round(np.diff([t for t, _ in plan]), 9))
    assert lens(a) == lens(b) and outs(a) == outs(b)
    # all gaps but the one after the last arrival are offered: n - 1 of the
    # same n, so at most one differs
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2
    assert [len(p["prompt"]) for _, p in a] != [len(p["prompt"])
                                                for _, p in b]
    assert a[0][0] == 0.0 and a[-1][0] < 40.0
    assert all(len(p["prompt"]) + p["max_new_tokens"] <= 2048 for _, p in a)
    assert min(lens(a)) >= 64 and max(lens(a)) <= 1536
    assert np.median(lens(a)) == pytest.approx(512, rel=0.02)


def test_the_same_seed_gives_the_same_requests():
    a = traffic_mod.open_plan(CHAT, 7, 10.0, 50257)
    b = traffic_mod.open_plan(CHAT, 7, 10.0, 50257)
    assert a == b
    assert max(t for _, p in a for t in p["prompt"]) < 50257


def test_closed_rounds_hold_the_same_lengths():
    spec = {"clients": 8, "max_total": 1024,
            "prompt_len": {"dist": "uniform", "min": 32, "max": 128},
            "output_len": {"dist": "uniform", "min": 384, "max": 896}}
    p1 = traffic_mod.ClosedPlan(spec, 3, 50257)
    p2 = traffic_mod.ClosedPlan(spec, 4, 50257)
    for k in (0, 5):
        l1 = sorted(len(p1.payload(c, k)["prompt"]) for c in range(8))
        l2 = sorted(len(p2.payload(c, k)["prompt"]) for c in range(8))
        assert l1 == l2
        assert l1[0] >= 32 and l1[-1] <= 128
    assert p1.payload(2, 1) == p1.payload(2, 1)


def test_seeds_above_int32_are_ordinary():
    big = 2 ** 31 + 252
    assert seeds.key_words(big, "weights").dtype == np.uint32
    assert 0 <= seeds.small_seed(big) < 2 ** 31
    assert list(seeds.key_words(big, "weights")) != list(
        seeds.key_words(big - 2 ** 32 if big >= 2 ** 32 else big + 1,
                        "weights"))
    assert seeds.rng(big, "x").integers(0, 10, 4).tolist() == seeds.rng(
        big, "x").integers(0, 10, 4).tolist()
