"""The float8 control, put in the program's place, must read far above
what the program reads, and a run with the control in the program's
place must come out not correct against the cell's own limits.  On the
CPU at a small size the program runs in bfloat16, as on the chip."""

import io

import pytest

from bench import harness, spec, weights
from bench.tests import tiny


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_far_above_the_program(seed):
    import numpy as np
    from bench import traffic

    ov = tiny.overrides("qwen1.5-1.8b.docqa", dtype="bfloat16")
    shape = spec.model_shape(ov["config"])
    ref = spec.reference(ov["config"]["reference"])
    params = weights.make(shape, seed)
    sched = traffic.schedule(ov["traffic"])
    prompt = traffic.prompt(seed, 0, sched, shape["vocab"])
    # the reference's own greedy tokens: their served gap is 0 by
    # construction, the control's is what it reads
    served = []
    for _ in range(24):
        cur = np.asarray(served, np.int64)
        lg = ref.logits(params, shape, prompt, np.append(cur, 0))
        served.append(int(np.asarray(lg[-1]).argmax()))
    g = ref.compare(params, shape, prompt, np.asarray(served), control=True)
    assert float(g["gap"].max()) == 0.0
    assert harness.logit_err(g["control_ends"], g["ends"]) > 0.01


@pytest.mark.parametrize("workload", ["qwen1.5-1.8b.docqa",
                                      "qwen2-1.5b.chat"])
def test_control_fails_the_cells_limits(workload):
    ov = tiny.overrides(workload, dtype="bfloat16")
    # the cell's own limits; only the count of served tokens is scaled
    # down to what a small run serves
    ov["checks"] = dict(spec.checks(workload), sample_requests=3,
                        min_served=5)
    out, err = io.StringIO(), io.StringIO()
    r = harness.run(workload, 2 ** 31 + 17, 1.5, False, control=True,
                    require_tpu=False, overrides=ov, out=out, err=err)
    assert r["correct"] is False
    assert set(r["checks"]) == {"logit_gap", "logit_err", "served_tokens"}
    assert r["checks"]["served_tokens"]["value"] >= 5
    assert any(r["checks"][k]["value"] > r["checks"][k]["limit"]
               for k in ("logit_gap", "logit_err"))
    assert '"correct": false' in out.getvalue().strip().splitlines()[-1]
