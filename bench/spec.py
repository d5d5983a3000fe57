"""Find a cell's parts by name.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
and a traffic mix.  Each part is a file of its own under ``bench/``:

* ``configs/<config>.json``  -- the model's published config.json keys,
  the engine's sparse-KV settings under ``serving``, and ``reference``,
  the name of the plain reference under ``references/``;
* ``traffic/<traffic>.json`` -- the mix's parameters and engine flags,
  read by the one generator in ``traffic.py``;
* ``checks/<workload>.json`` -- the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``metrics/<metric>.py``    -- one reader per per-layer metric, found
  by the metric's full name or, failing that, by the part before its
  first dot (``step_device_ms.chat`` -> ``step_device_ms.py``);
* ``peaks.json``             -- the chip's peaks, keyed by device_kind.

A later cell is new files plus a ``workloads`` entry: nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def checks(workload_name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "checks",
                                   f"{workload_name}.json"))


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def metrics_for(workload_name: str, kind: str,
                bench: dict | None = None) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` entries: those that list
    the cell under ``workloads``, or list no cells at all."""
    bench = bench or benchmark()
    return [m for m in bench[kind]
            if workload_name in m.get("workloads", [workload_name])]


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx)`` function of a per-layer metric."""
    d = os.path.join(BENCH_DIR, "metrics")
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(d, f"{stem}.py")
        if os.path.exists(path):
            return _load_module(path, f"bench_metric_{stem}").read
    raise KeyError(f"no reader for per-layer metric {metric!r} in "
                   "bench/metrics/")


def reference(name: str):
    """The plain reference module named by a configuration."""
    return _load_module(os.path.join(BENCH_DIR, "references", f"{name}.py"),
                        f"bench_reference_{name}")


# config.json's ``hidden_act`` -> the gated MLP it names
ACTIVATIONS = {"silu": "swiglu", "gelu_pytorch_tanh": "geglu"}


def model_shape(cfg: dict) -> dict:
    """The sizes and options the harness, the reference and the cost
    counts share, read from the published config.json keys.  A config
    without ``attention_bias`` (Qwen2's has none) carries q/k/v biases,
    as the Qwen2 modeling code does."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["hidden_act"] not in ACTIVATIONS:
        raise ValueError(f"unsupported hidden_act {cfg['hidden_act']!r}")
    return {
        "n_layers": cfg["num_hidden_layers"],
        "d_model": d,
        "n_heads": h,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", d // h),
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
        "qkv_bias": bool(cfg.get("attention_bias", True)),
        "act": ACTIVATIONS[cfg["hidden_act"]],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "kv_page": cfg["serving"]["kv_page"],
        "kv_topk_pages": cfg["serving"]["kv_topk_pages"],
        "dtype_bytes": 2 if cfg["torch_dtype"] == "bfloat16" else 4,
    }


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig

    s = model_shape(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=s["n_layers"],
        d_model=s["d_model"], n_heads=s["n_heads"],
        n_kv_heads=s["n_kv_heads"], d_ff=s["d_ff"], vocab=s["vocab"],
        head_dim=s["head_dim"], act=s["act"], qkv_bias=s["qkv_bias"],
        tie_embeddings=s["tie_embeddings"], rope_theta=s["rope_theta"],
        norm_eps=s["norm_eps"], kv_page=s["kv_page"],
        kv_topk_pages=s["kv_topk_pages"],
        kv_dtype=cfg["serving"]["kv_dtype"],
        param_dtype=cfg["torch_dtype"])
