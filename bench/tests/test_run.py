"""A whole run on the CPU at a small size: the harness refuses to report
there, and with the chip check skipped it decides ``correct`` from the
served tokens."""

import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, spec
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["qwen1.5-1.8b.docqa", "qwen2-1.5b.chat"]


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_refuses_to_report_on_the_cpu():
    p = _cli(ROOT, "--workload", CELLS[1], "--seed", "3", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--workload", CELLS[0], "--seed", "3", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def _run(workload, seed, **kw):
    out, err = io.StringIO(), io.StringIO()
    r = harness.run(workload, seed, 1.5, False, require_tpu=False,
                    overrides=tiny.overrides(workload, **kw), out=out,
                    err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == r
    return r, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("workload", CELLS)
def test_run_is_correct_and_repeats(workload):
    r, out, err = _run(workload, 2 ** 31 + 11)
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    assert "check logit_gap" in err
    assert r["checks"]["logit_gap"]["value"] <= 1e-3
    work = json.loads(out.split("WORK ", 1)[1].splitlines()[0])
    assert work["preemptions"] == 0 and work["compiles_in_window"] == 0
    assert set(r["metrics"]) == {m["name"] for m in spec.metrics_for(
        workload, "end_to_end")}


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serve.engine import PagedEngine

    commit = PagedEngine._commit_decode

    def altered(self, pairs, logits, sel, rb, esel=None):
        # the argmax moves to the next vocabulary id
        return commit(self, pairs, np.roll(np.asarray(logits), 1, axis=-1),
                      sel, rb, esel)

    monkeypatch.setattr(PagedEngine, "_commit_decode", altered)
    r, _, _ = _run(CELLS[0], 5)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > 1e-3


@pytest.mark.parametrize("keys", [
    {"attention_bias": False},
    {"hidden_act": "gelu_pytorch_tanh"},
])
def test_the_configurations_keys_choose_the_architecture(keys):
    r, _, _ = _run(CELLS[1], 2 ** 31 + 23, **keys)
    assert r["correct"] is True
    shape = spec.model_shape(tiny.overrides(CELLS[1], **keys)["config"])
    arch = spec.arch_config(tiny.overrides(CELLS[1], **keys)["config"])
    from bench import weights
    has_bias = keys.get("attention_bias", True)
    assert arch.qkv_bias is has_bias and shape["qkv_bias"] is has_bias
    assert ("bq" in weights.make(shape, 1)["layers"]) is has_bias
    assert arch.act == {"silu": "swiglu", "gelu_pytorch_tanh": "geglu"}[
        keys.get("hidden_act", "silu")]


def test_whole_prompt_hits_copy_on_write_and_are_compared():
    ov = tiny.overrides(CELLS[0])
    ov["traffic"]["documents"] = dict(ov["traffic"]["documents"],
                                      bare_share=0.3)
    out, err = io.StringIO(), io.StringIO()
    r = harness.run(CELLS[0], 2 ** 31 + 29, 1.5, False, require_tpu=False,
                    overrides=ov, out=out, err=err)
    work = json.loads(out.getvalue().split("WORK ", 1)[1].splitlines()[0])
    assert work["cow_copies"] > 0 and work["preemptions"] == 0
    assert r["correct"] is True


def test_the_sample_holds_the_longest_and_a_whole_prompt_hit():
    from types import SimpleNamespace as R

    reqs = [R(rid=i, out_tokens=[0] * (10 + i), prompt_len=60,
              cached_tokens=48) for i in range(6)]
    reqs.append(R(rid=6, out_tokens=[0] * 3, prompt_len=48,
                  cached_tokens=47))
    reqs.append(R(rid=7, out_tokens=[0] * 3, prompt_len=20,
                  cached_tokens=0))
    for seed in range(4):
        got = harness.sample_requests(reqs, 3, seed)
        assert [r.rid for r in got[:2]] == [5, 6] and len(got) == 3
    chat = [R(rid=i, out_tokens=[0] * i, prompt_len=9, cached_tokens=0)
            for i in range(5)]
    got = harness.sample_requests(chat, 3, 1)
    assert got[0].rid == 4 and len({r.rid for r in got}) == 3
