"""FLOP and byte functions against numbers worked by hand for gpt2-medium
(24 layers, d 1024, inner 4096, vocabulary 50257), and the open-loop
schedule as a pure function of the seed."""

import json
import os

import numpy as np
import pytest

from benchmarks.drivers import serve_closed, serve_open
from benchmarks.families import gpt as fam

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs")


@pytest.fixture(scope="module")
def medium():
    return json.load(open(os.path.join(CONFIGS, "gpt2-medium.json")))


def test_train_flops_per_token(medium):
    # blocks: 4*1024^2 + 2*1024*4096 = 12,582,912 weights a layer, x24
    # = 301,989,888; forward+backward 6 FLOPs a weight = 1,811,939,328
    # causal attention: 12 * 24 * 1024 * 1024 / 2 = 150,994,944
    # head: 6 * 1024 * 50257 = 308,779,008
    assert fam.train_flops_per_token(medium, 1024) == \
        1_811_939_328 + 150_994_944 + 308_779_008     # 2.27 GFLOP a token


def test_flash_flops_per_step(medium):
    # per layer, batch 8: 7 matmuls of 2 * 8 * 16 heads * 1024^2 * 64, halved
    per_layer = 7 * 2 * 8 * 16 * 1024 * 1024 * 64 / 2
    assert fam.flash_flops_per_step(medium, 8, 1024) == 24 * per_layer
    # the cell runs with remat: forward twice, dq, dkv in each of 24 layers
    assert fam.flash_calls_per_step(medium) == 96
    assert fam.flash_calls_per_step(
        dict(medium, run=dict(medium["run"], remat=False))) == 72


def test_decode_min_bytes(medium):
    # weights at 2 bytes: (301,989,888 + 1024*50257) * 2 = 706,906,112
    # keys+values per position: 2 * 24 layers * 32 rows * 1024 * 2 B = 3,145,728
    # 127 decode steps at positions 897..1023: sum = 127 * 960 = 121,920
    weights = 706_906_112
    assert fam.decode_min_bytes(medium, 32, 896, 128) == \
        weights * 128 + 3_145_728 * 121_920
    # no decode step when one token is asked for: the prefill's weights only
    assert fam.decode_min_bytes(medium, 32, 896, 1) == weights


def test_schedule_is_a_pure_function_of_the_seed():
    a = serve_open.schedule(3, 16.0, 30.0, 7)
    assert np.array_equal(a, serve_open.schedule(3, 16.0, 30.0, 7))
    assert not np.array_equal(a, serve_open.schedule(4, 16.0, 30.0, 7))
    assert (np.diff(a) > 0).all() and a[0] > 0 and a[-1] < 30.0
    # another phase: the same gaps between arrivals, turned round the window
    b = serve_open.schedule(3, 16.0, 30.0, 8)
    assert not np.array_equal(a, b)
    ring = lambda t: np.sort(np.diff(np.append(t, t[0] + 30.0)))
    assert np.allclose(ring(a), ring(b))
    # a Poisson process of rate 16 over 30 s conditioned on its count
    assert len(a) == 480
    gaps = np.diff(a)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_block_percentile_is_a_median_of_readings():
    lat = np.arange(600, dtype=float)
    assert serve_open.block_percentile(lat, 95, 1) == np.percentile(lat, 95)
    flat = np.full(600, 100.0)
    flat[250:290] = 5000.0            # one slow stretch, 40 of 600 requests
    assert np.percentile(flat, 95) > 4000          # owns the window's tail
    assert serve_open.block_percentile(flat, 95, 6) == 100.0   # one reading
    assert serve_open.block_percentile(flat[:3], 95, 6) == 100.0


def test_seconds_per_request_reads_pairs_and_alternation_alike():
    alternate = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    pairs = [2.0, 2.0, 4.0, 4.0, 6.0, 6.0]
    for returned in (alternate, pairs, pairs[::-1]):
        assert serve_closed.seconds_per_request(returned, 2) == [1.0] * 4
    assert serve_closed.seconds_per_request(alternate, 1) == [1.0] * 5
    assert serve_closed.seconds_per_request([1.0, 2.0], 2) == []
    stalled = [1.0, 2.0, 3.0, 9.0, 10.0, 11.0, 12.0]       # one stall of 5 s
    assert np.median(serve_closed.seconds_per_request(stalled, 2)) == 1.0


def test_batches_are_a_pure_function_of_the_seed():
    a = fam.lm_batches(50257, 2, 64, 5, 2)
    b = fam.lm_batches(50257, 2, 64, 5, 2)
    assert all(np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, b))
    assert a[0]["ids"].min() >= 3           # pad, bos and eos never drawn
    assert np.array_equal(a[0]["ids"][:, 1:], a[0]["labels"][:, :-1])
