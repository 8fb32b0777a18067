"""The yardstick's arithmetic (ISSUE 24): percentiles and time per token on
hand-made lists, the traffic plans from a seed, the exposition parser, the
decode-bytes function against PR 22's compiled sizes, and the per-layer
readers on hand-made inputs.  Nothing here touches JAX."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import closed_loop, open_loop_poisson  # noqa: E402
from benchmark.lib import lengths, model_bytes, peaks, prom, stats  # noqa: E402
from benchmark.lib.load import Rec  # noqa: E402
from benchmark.lib.readers import READERS  # noqa: E402
from benchmark.lib.server import log_seconds, words  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# ---- percentiles, time per token, spread ----------------------------------

@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 95, 4.8),
    ([5, 1, 4, 2, 3], 0, 1.0),
    ([5, 1, 4, 2, 3], 100, 5.0),
    ([10.0], 95, 10.0),
    (list(range(1, 101)), 95, 95.05),
    ([], 95, None),
])
def test_percentile(values, q, want):
    got = stats.percentile(values, q)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("first,last,n,want", [
    (1.0, 3.0, 101, 20.0),        # 100 gaps in 2 s
    (0.0, 0.7, 8, 100.0),         # the smallest request that counts
    (0.0, 0.7, 7, None),          # fewer than 8 tokens: left out
    (None, None, 50, None),       # no token at all
    (2.0, 2.0, 1, None),
])
def test_tpot_per_request(first, last, n, want):
    got = stats.tpot_ms(first, last, n)
    assert got is None if want is None else got == pytest.approx(want)


def test_tpot_is_per_request_not_per_gap():
    # tokens arrive in bursts of 8 every 200 ms: a per-gap percentile would
    # read 0 or 200 ms; the per-request figure is the decode rate, 25 ms
    times = [0.2 * (i // 8) for i in range(64)]
    assert stats.tpot_ms(times[0], times[-1], 64) == pytest.approx(
        1400 / 63)


def test_iqr_share_is_the_contracts_spread():
    vals = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4) of these: 100.75 and 104.25
    assert stats.iqr_share(vals) == pytest.approx(3.5 / 102.5)


def test_summary_counts_samples():
    s = stats.summary([1, None, 3])
    assert s["count"] == 2 and s["max"] == 3


# ---- traffic from a seed ----------------------------------------------------

CHAT = {"generator": "open_loop_poisson", "rate_rps": 4.0, "warm_seconds": 8,
        "pool_seed": 24, "drain_seconds": 30,
        "prompt_tokens": {"dist": "lognormal", "median": 200, "sigma": 1.0,
                          "min": 24, "max": 1536},
        "max_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 8, "max": 512}}
SAT = {"generator": "closed_loop", "clients": 64, "pool_seed": 24,
       "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.5,
                         "min": 32, "max": 512},
       "max_tokens": {"dist": "uniform", "min": 192, "max": 320}}


def window(plan, warm=8.0):
    return [r for r in plan["requests"] if r["due_s"] >= warm]


def test_every_seed_offers_the_same_schedule():
    # PR 24 measured it: the same multiset of sizes and gaps in a shuffled
    # or rotated order moved the tails by 15% and more, so the run's seed
    # makes the text, the sampling seeds and the weights, not the schedule
    assert open_loop_poisson.plan(CHAT, 1, 40) == open_loop_poisson.plan(
        CHAT, 2, 40)
    assert closed_loop.plan(SAT, 1, 40) == closed_loop.plan(SAT, 2, 40)


def test_the_schedule_follows_the_traffic_files_pool_seed():
    other = dict(CHAT, pool_seed=25)
    assert open_loop_poisson.plan(CHAT, 1, 40) != open_loop_poisson.plan(
        other, 1, 40)


def test_unrecorded_traffic_does_not_depend_on_the_windows_length():
    a = open_loop_poisson.plan(CHAT, 1, 40)["requests"]
    b = open_loop_poisson.plan(CHAT, 1, 20)["requests"]
    assert [r for r in a if r["due_s"] < 8] == [r for r in b
                                                if r["due_s"] < 8]


def test_open_loop_fills_the_horizon_at_the_rate():
    reqs = open_loop_poisson.plan(CHAT, 5, 40)["requests"]
    assert len(reqs) == round(4.0 * 8) + round(4.0 * 40)
    assert reqs[0]["due_s"] == 0.0
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(reqs, reqs[1:]))
    assert len(window(open_loop_poisson.plan(CHAT, 5, 40))) == 160
    assert window(open_loop_poisson.plan(CHAT, 5, 40))[0]["due_s"] == 8.0
    assert 40 < reqs[-1]["due_s"] < 48


def test_open_loop_gaps_are_exponential_not_even():
    reqs = open_loop_poisson.plan(CHAT, 5, 40)["requests"]
    gaps = [y["due_s"] - x["due_s"] for x, y in zip(reqs, reqs[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.7 < var ** 0.5 / mean < 1.4       # exponential: cv = 1


@pytest.mark.parametrize("params,key", [(CHAT, "prompt_tokens"),
                                        (CHAT, "max_tokens"),
                                        (SAT, "prompt_tokens"),
                                        (SAT, "max_tokens")])
def test_lengths_stay_inside_their_clip(params, key):
    pool = lengths.pool(params, 2000)
    vals = [p if key == "prompt_tokens" else m for p, m in pool]
    assert min(vals) >= params[key]["min"]
    assert max(vals) <= params[key]["max"]
    assert len(set(vals)) > 50


def test_chat_sizes_fit_the_sequence_limit():
    for p, m in lengths.pool(CHAT, 5000):
        assert p + m <= 2048


def test_saturated_prompts_are_one_prefill_chunk():
    assert max(p for p, _ in lengths.pool(SAT, 5000)) <= 512


def test_closed_loop_plan():
    a = closed_loop.plan(SAT, 7, 40)
    assert a["clients"] == 64 and len(a["requests"]) == closed_loop.POOL
    assert [r["idx"] for r in a["requests"][:3]] == [0, 1, 2]


@pytest.mark.parametrize("n", [1, 5, 13, 241, 1517])
def test_words_is_exactly_n_bytes(n):
    text = words(n, 42)
    assert len(text.encode()) == n and text.isascii()


def test_words_part_inside_the_first_cache_page():
    # a page holds 16 tokens, 7 of them the template's: 9 bytes of text
    heads = {words(200, s)[:9] for s in range(500)}
    assert len(heads) > 490


def test_unknown_distribution_is_an_error():
    import random
    with pytest.raises(ValueError):
        lengths.draw({"dist": "zipf"}, random.Random(0))


# ---- a request as the client saw it -----------------------------------------

@pytest.mark.parametrize("kw,bad", [
    (dict(n_tokens=200, finish="length", done=True), False),
    (dict(n_tokens=57, finish="stop", done=True), False),
    (dict(n_tokens=57, finish="length", done=True), True),
    (dict(n_tokens=201, finish="length", done=True), True),
    (dict(n_tokens=0, finish=None, done=True), True),
    (dict(n_tokens=10, finish="length", done=False), True),
    (dict(n_tokens=200, finish="length", done=True, error="HTTP 503"), True),
    (dict(n_tokens=200, finish="error", done=True), True),
])
def test_well_formed_response(kw, bad):
    rec = Rec(0, 100, 200, **kw)
    assert (rec.malformed() is not None) == bad


# ---- the server's exposition -------------------------------------------------

EXPO = """# HELP helix_generated_tokens_total x
# TYPE helix_generated_tokens_total counter
helix_generated_tokens_total{model="m"} 1200
helix_generated_tokens_total{model="other"} 7
helix_queue_wait_seconds_bucket{model="m",le="0.1"} 3
helix_queue_wait_seconds_sum{model="m"} 0.5
helix_queue_wait_seconds_count{model="m"} 4
helix_unlabelled 2.5e3
"""


def test_prom_parse_keeps_the_model_and_drops_buckets():
    got = prom.parse(EXPO, "m")
    assert got == {"helix_generated_tokens_total": 1200.0,
                   "helix_queue_wait_seconds_sum": 0.5,
                   "helix_queue_wait_seconds_count": 4.0,
                   "helix_unlabelled": 2500.0}


def test_prom_delta_and_histogram_mean():
    a = prom.parse(EXPO, "m")
    b = dict(a, helix_generated_tokens_total=1500.0,
             helix_queue_wait_seconds_sum=0.9,
             helix_queue_wait_seconds_count=8.0)
    assert prom.delta(a, b, "helix_generated_tokens_total") == 300
    assert prom.delta(a, b, "missing") is None
    assert prom.mean_of_histogram_ms(
        a, b, "helix_queue_wait_seconds") == pytest.approx(100.0)
    assert prom.mean_of_histogram_ms(a, a, "helix_queue_wait_seconds") is None


def test_log_seconds_reads_the_load_lines():
    log = ("INFO model x: weights on device in 14.1s (int8, 7.62 GB)\n"
           "INFO engine x: warmup() in 38.6s\n")
    assert log_seconds(log, "weights on device") == 14.1
    assert log_seconds(log, r"warmup\(\)") == 38.6
    assert log_seconds(log, "absent") is None


# ---- bytes and peaks ---------------------------------------------------------

def test_qwen2_weight_bytes_match_pr22_compiled_size():
    # PERF.md section 5 (compiled, PR 22): 7.62 GB of weights
    assert model_bytes.weight_bytes(config("qwen2-7b-int8")) == pytest.approx(
        7.62e9, rel=2e-3)


def test_qwen2_page_bytes_match_pr22():
    assert model_bytes.page_bytes(config("qwen2-7b-int8"), 16) == 917504


def test_mistral_page_is_two_mib_and_kv_is_2_3x_qwen2():
    q, m = config("qwen2-7b-int8"), config("mistral-7b-v03-int8")
    assert model_bytes.page_bytes(m, 16) == 2 * 2**20
    ratio = model_bytes.kv_bytes_per_token(m) / model_bytes.kv_bytes_per_token(q)
    assert ratio == pytest.approx(2.2857, rel=1e-3)


def test_decode_step_reads_weights_once_and_live_kv():
    q = config("qwen2-7b-int8")
    base = model_bytes.decode_step_bytes(q, 0)
    # all but the embedding table (152064 x 3584 int8 + scales)
    table = 152064 * 3584 + 152064 * 4
    assert base == model_bytes.weight_bytes(q) - table
    more = model_bytes.decode_step_bytes(q, 32 * 300)
    assert more - base == 32 * 300 * 57344


def test_unknown_device_kind_is_an_error_not_a_default():
    assert peaks.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.chip_peaks("TPU v9")


# ---- per-layer readers on hand-made inputs -----------------------------------

def ctx(**kw):
    base = {"scrapes": {"w0": {}, "w1": {}}, "flight": [], "trace": None,
            "log": "", "recs": [], "config": config("qwen2-7b-int8"),
            "device_kind": "TPU v5 lite"}
    base.update(kw)
    return base


def test_reader_finds_nothing_returns_none():
    for name, spec in [
            ("histogram_mean_ms", {"series": "helix_queue_wait_seconds"}),
            ("send_lag_p95_ms", {}), ("flight_slot_occupancy", {}),
            ("ttft_percentile_ms", {"q": 95}),
            ("log_seconds", {"pattern": "warmup in ([0-9.]+)s"}),
            ("trace_idle_share", {}),
            ("trace_op_share", {"op": "attention"}),
            ("trace_program_ms", {"program": "step_fn"}),
            ("decode_hbm_share", {"program": "step_fn"})]:
        assert READERS[name](ctx(), spec) is None, name


def test_send_lag_is_sent_minus_due():
    recs = [Rec(i, 10, 10, due=100.0 + i, sent=100.0 + i + 0.001 * i)
            for i in range(21)]
    assert READERS["send_lag_p95_ms"](ctx(recs=recs), {}) == pytest.approx(19)


def test_ttft_percentile_is_from_due_time():
    recs = [Rec(i, 10, 10, due=50.0, sent=50.001, first=50.0 + 0.01 * i)
            for i in range(1, 101)]
    got = READERS["ttft_percentile_ms"](ctx(recs=recs), {"q": 95})
    assert got == pytest.approx(950.5)


def test_slot_occupancy_is_per_step_mean():
    flight = [{"slots_busy": 32, "slots_total": 32},
              {"slots_busy": 16, "slots_total": 32}]
    assert READERS["flight_slot_occupancy"](ctx(flight=flight), {}) == 75.0


TRACE = {"window_s": 2.0, "devices": [{
    "busy_s": 1.5,
    "ops": {"attn_kernel": [10, 0.3, 0.3], "fusion.1": [10, 1.2, 1.2]},
    "modules": [
        {"name": "jit_step_fn(1)", "dur_s": 0.2, "ops": {"attn_kernel": 224}},
        {"name": "jit_step_fn(1)", "dur_s": 0.1, "ops": {"attn_kernel": 112}},
        {"name": "jit_step_fn(2)", "dur_s": 0.09,
         "ops": {"attn_kernel": 28, "flash": 28}},
        {"name": "jit_other(3)", "dur_s": 5.0, "ops": {}},
    ]}]}


def test_trace_idle_and_kernel_share():
    c = ctx(trace=TRACE)
    assert READERS["trace_idle_share"](c, {}) == pytest.approx(25.0)
    assert READERS["trace_op_share"](c, {"op": "^attn_"}) == pytest.approx(20.0)


def test_decode_ms_divides_a_fused_window_by_its_steps():
    spec = {"program": "step_fn", "without_op": "flash",
            "per_op": "^attn_kernel$"}
    # 224 kernel calls over 28 layers = 8 steps in 0.2 s; 112 = 4 in 0.1 s
    assert READERS["trace_program_ms"](ctx(trace=TRACE), spec) == \
        pytest.approx(25.0)
    spec = {"program": "step_fn", "with_op": "flash"}
    assert READERS["trace_program_ms"](ctx(trace=TRACE), spec) == \
        pytest.approx(90.0)


def test_decode_hbm_share_is_bytes_over_time_over_peak():
    spec = {"program": "step_fn", "without_op": "flash",
            "per_op": "^attn_kernel$"}
    flight = [{"kind": "decode", "kv_pages_used": 600}]
    got = READERS["decode_hbm_share"](ctx(trace=TRACE, flight=flight), spec)
    need = model_bytes.decode_step_bytes(
        config("qwen2-7b-int8"), 600 * 16, embed_rows=32)
    assert got == pytest.approx(100 * need / 0.025 / 819e9)
    assert 30 < got < 100
