"""BENCHMARK.json names only parts that exist under bench/."""

import os
import re

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_part_is_found_by_name():
    b = spec.benchmark()
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert spec.config(c["name"])["name"] == c["name"]
        assert spec.reference(spec.config(c["name"])["reference"]).compare
    for w in b["workloads"]:
        assert w["config"] in configs
        assert spec.traffic(w["traffic"])["engine"]
        assert "limit" in spec.checks(w["name"])["logit_gap"]
        kinds = {m["name"] for m in spec.metrics_for(w["name"], "end_to_end",
                                                     b)}
        assert "setup_s" in kinds and len(kinds) >= 2
        assert spec.metrics_for(w["name"], "per_layer", b)
    for m in b["per_layer"]:
        assert callable(spec.reader(m["name"]))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_metric_names_and_moves():
    b = spec.benchmark()
    e2e = {m["name"] for m in b["end_to_end"]}
    assert e2e == {"output_tok_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            moved = {x["name"] for x in spec.metrics_for(w, "end_to_end", b)}
            assert m["moves"] in moved
