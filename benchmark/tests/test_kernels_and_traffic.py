"""Each kernel's operations-and-bytes function against a hand count at
one shape, the peaks, and the traffic generator's seeding."""

import json

import numpy as np
import pytest

from benchmark import harness, roofline, traffic
from benchmark.families import gpt2, llama
from benchmark.kernels import tl_flash, tl_paged_decode
from benchmark.harness import HERE

MISTRAL = json.loads((HERE / "configs" / "mistral7b-l16.json").read_text())
GPT2M = json.loads((HERE / "configs" / "gpt2-medium.json").read_text())


def test_paged_decode_work_hand_count():
    # 4 rows, one query each, 300 live tokens each, bf16:
    # pairs 1200; flops 4 * 32 * 128 * 1200; K+V 2 * 8 * 128 * 2 B a token
    f, b = tl_paged_decode.work(MISTRAL, 4, 1200, 1200)
    assert f == 4 * 32 * 128 * 1200 == 19_660_800
    assert b == 1200 * 4096 + 4 * 2 * 32 * 128 * 2 == 4_980_736
    peaks = harness.peaks_for("TPU v5 lite")
    t, bound = roofline.least_seconds(f, b, peaks)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


@pytest.mark.parametrize("kernel,matmuls,arrays", [
    ("tl_flash_fwd", 2, 4), ("tl_flash_bwd_dq", 3, 5),
    ("tl_flash_bwd_dkv", 4, 6),
])
def test_flash_work_hand_count(kernel, matmuls, arrays):
    # 4 rows x 16 heads x 1024 x 64, causal: half of 1024^2 pairs
    f, b = tl_flash.work(kernel, 4, 16, 1024, 64)
    assert f == matmuls * 2 * 64 * (4 * 16 * 1024 * 1024 // 2)
    assert b == arrays * 4 * 16 * 1024 * 64 * 2


def test_model_counts():
    # Mistral-7B layer: 4096*4096*2 + 4096*1024*2 + 3*4096*14336 = 218.1 M
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert llama.matmul_params(MISTRAL) == 16 * layer + 4096 * 32000
    assert llama.kv_bytes_per_token_layer(MISTRAL) == 4096
    assert llama.attn_flops(MISTRAL, 100) == 4 * 16 * 32 * 128 * 100
    # GPT-2 medium: 24 * 12 * 1024^2 + 1024 * 50257
    assert gpt2.matmul_params(GPT2M) == 24 * 12 * 1024 ** 2 + 1024 * 50257
    per_tok = gpt2.train_flops_per_token(GPT2M, 1024)
    assert per_tok == pytest.approx(3 * (2 * 353_453_056 + 4 * 24 * 1024 * 512.5))


def test_peaks_unknown_device_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(harness.BenchFailure):
            harness.peaks_for(kind)


def test_percentile_is_nearest_rank_over_all():
    v = list(range(1, 101))
    assert harness.percentile(v, 0.95) == 95
    assert harness.percentile(v, 0.5) == 50
    assert harness.percentile([3.0], 0.95) == 3.0


MIX = json.loads((HERE / "traffic" / "decode_heavy.json").read_text())


def _take(seed, n=70):
    s = traffic.RequestStream(MIX, 32000, seed)
    return [next(s) for _ in range(n)]


def test_same_seed_same_requests_other_seed_other_requests():
    a, b, c = _take(5), _take(5), _take(2**31 + 11)
    assert all(
        np.array_equal(x.ids, y.ids) and x.max_new == y.max_new
        for x, y in zip(a, b)
    )
    assert any(not np.array_equal(x.ids[:8], y.ids[:8]) for x, y in zip(a, c))
    # a seed walks its own table round and round
    sizes = lambda reqs: [(len(r.ids), r.max_new) for r in reqs]
    assert sizes(a) == (traffic.size_table(MIX, 5) * 2)[:70]


def test_every_seed_sends_the_same_lengths_in_an_order_of_its_own():
    n = MIX["distinct_sizes"]
    t5, t6 = traffic.size_table(MIX, 5), traffic.size_table(MIX, 2**31 + 11)
    assert t5 != t6 and len(t5) == len(t6) == n
    for col in (0, 1):  # the same prompt lengths, the same output lengths
        assert sorted(x[col] for x in t5) == sorted(x[col] for x in t6)
    assert sorted(x[0] for x in t5) == sorted(
        traffic.lengths(MIX["prompt_tokens"], n)
    )


def test_lengths_follow_the_file():
    p, o = MIX["prompt_tokens"], MIX["output_tokens"]
    n = MIX["distinct_sizes"]
    prompts, outs = traffic.lengths(p, n), traffic.lengths(o, n)
    assert min(prompts) >= p["min"] and max(prompts) <= p["max"]
    assert abs(np.median(prompts) - p["median"]) <= 0.05 * p["median"]
    assert min(outs) >= o["min"] and max(outs) <= o["max"]
    assert abs(np.median(outs) - o["median"]) <= 0.05 * o["median"]
    with pytest.raises(ValueError):
        traffic.lengths(dict(p, dist="zipf"), n)


def test_train_batches_differ_by_step_and_row():
    mix = json.loads((HERE / "traffic" / "train_lm_s1024.json").read_text())
    a = traffic.train_batch(mix, 50257, 9, 0)
    assert a.shape == (16, 1025) and a.dtype == np.int32
    assert np.array_equal(a, traffic.train_batch(mix, 50257, 9, 0))
    assert not np.array_equal(a, traffic.train_batch(mix, 50257, 9, 1))
    assert len({r.tobytes() for r in a}) == 16
