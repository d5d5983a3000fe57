"""The trace-to-metrics reduction on a small trace recorded on a TPU v5e.

The fixture holds three iterations of ``bench.step`` (two small jitted
programs: a 1024x1024 bf16 matmul with tanh, and a reduction) inside
``bench.window``, each followed by ``bench.count`` sleeping 2 ms.  The
first iteration's device ops precede the window span on the trace's
clock, so they fall outside it.
"""

import os

import pytest

from bench import trace
from bench.metrics import _costs, device_idle_share, step_device_ms
from bench.harness import Iteration

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(FIXTURE))


def test_window_and_busy(reduced):
    # bench.window: 44,903,748 .. 57,085,688 ns
    assert reduced["window_s"] == pytest.approx(12_181_940e-9)
    # ops of iterations 2 and 3 (ns): copy-start 14 + copy-done 3,107
    # + fusion 12,600 + reduce 4,830, then 13 + 3,160 + 12,601 + 5,093
    assert reduced["busy_s"] == pytest.approx(41_418e-9)


def test_top_ops(reduced):
    names = [n.split(" = ")[0] for n, _ in reduced["device_ops"]]
    assert names[0] == "%fusion"
    assert reduced["device_ops"][0][1] == pytest.approx(25_201e-9)
    assert names[1] == "%multiply_reduce_fusion"
    assert reduced["device_ops"][1][1] == pytest.approx(9_923e-9)


def test_idle_gaps_sum_to_the_idle_time(reduced):
    idle = sum(v for _, v in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    # the 2 ms sleeps under bench.count are the longest gaps
    assert reduced["idle_gaps"][0][0] == "bench.count"


def test_readers(reduced):
    ctx = _costs.Context(iters=[Iteration(tick=k, t0=0, t1=0)
                                for k in range(3)],
                         window_s=reduced["window_s"], trace=reduced,
                         shape={}, peaks={})
    assert step_device_ms.read(ctx) == pytest.approx(1e3 * 41_418e-9 / 3)
    assert device_idle_share.read(ctx) == pytest.approx(
        100 * (1 - 41_418 / 12_181_940))
    ctx.trace = None
    assert step_device_ms.read(ctx) is None
