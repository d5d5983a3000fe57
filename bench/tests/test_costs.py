"""FLOP and byte counts against numbers worked out by hand."""

import pytest

from bench import spec
from bench.metrics import _costs
from bench.harness import Iteration


@pytest.fixture(params=["qwen1.5-1.8b", "qwen2-1.5b"])
def shape(request):
    return spec.model_shape(spec.config(request.param))


# per layer: d*q + 2*d*kv + q*d + 3*d*f
#   qwen1.5-1.8b: 2048*2048*4 + 3*2048*5504           = 50,593,792
#   qwen2-1.5b:   1536*1536*2 + 2*1536*256 + 3*1536*8960 = 46,792,704
LAYER = {"qwen1.5-1.8b": 50_593_792, "qwen2-1.5b": 46_792_704}
# bf16 weights a step reads: 24 * (50,593,792 + 3*2048 bias) * 2
#   + head 2048*151936*2 + norms (2*24+1)*2048*4       = 3,051,528,192
#   qwen2: 28 * (46,792,704 + 2048) * 2 + 1536*151936*2
#   + (2*28+1)*1536*4                                    = 3,087,603,712
WEIGHTS = {"qwen1.5-1.8b": 3_051_528_192, "qwen2-1.5b": 3_087_603_712}


def test_layer_and_weight_bytes(shape):
    name = {2048: "qwen1.5-1.8b", 1536: "qwen2-1.5b"}[shape["d_model"]]
    assert _costs.layer_matmul_params(shape) == LAYER[name]
    assert _costs.weight_bytes(shape) == WEIGHTS[name]


def test_decode_row_beyond_the_cover():
    s = spec.model_shape(spec.config("qwen1.5-1.8b"))
    # pos 6200: 388 valid pages, 64 selected, 1024 tokens attended
    # flops: 2*24*50,593,792 + 2*2048*151936
    #        + 24*(4*16*128*1024 + 2*16*128*388)   = 3,290,300,416
    # bytes: 64 pages * 16 tokens * 196,608 B (K+V, all layers)
    #        + 24*16*388*128*4 summaries + 196,608 written = 277,807,104
    f, b = _costs.decode_row(s, 6200)
    assert f == 3_290_300_416
    assert b == 277_807_104


def test_decode_row_inside_the_cover():
    s = spec.model_shape(spec.config("qwen2-1.5b"))
    # pos 99: 7 pages, all kept, 100 tokens attended
    # flops: 2*28*46,792,704 + 2*1536*151936
    #        + 28*(4*12*128*100 + 2*12*128*7)
    #      = 2,620,391,424 + 466,747,392 + 17,805,312 = 3,104,944,128
    # bytes: 7*16*28,672 + 28*2*7*128*4 + 28,672   = 3,440,640
    f, b = _costs.decode_row(s, 99)
    assert f == 3_104_944_128
    assert b == 3_440_640


def test_prefill_chunk():
    s = spec.model_shape(spec.config("qwen2-1.5b"))
    # 256 tokens from 512: causal sum 256*512 + 256*257/2 = 163,968
    # flops: 2*28*46,792,704*256 + 2*1536*151936 + 28*4*12*128*163,968
    #      = 670,820,204,544 + 466,747,392 + 28,207,742,976
    #      = 699,494,694,912
    # bytes: (768 + 256) * 28,672                  = 29,360,128
    f, b = _costs.prefill_chunk(s, 512, 256)
    assert f == 699_494_694_912
    assert b == 29_360_128


def test_iteration_least_time():
    s = spec.model_shape(spec.config("qwen1.5-1.8b"))
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    idle = Iteration(tick=1, t0=0.0, t1=1.0)
    assert _costs.iteration(s, idle) == (0.0, 0.0)
    it = Iteration(tick=2, t0=0.0, t1=1.0, decode_pos=[6200] * 32)
    f, b = _costs.iteration(s, it)
    assert f == 32 * 3_290_300_416
    assert b == 3_051_528_192 + 32 * 277_807_104
    t, bound = _costs.least_time_s(s, peaks, it)
    assert bound == "memory"
    assert t == pytest.approx(b / 819e9)
