"""Small stand-ins of the cells, for driving a whole run on the CPU."""

from bench import spec

PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def overrides(workload: str, dtype: str = "float32",
              limit: float = 1e-3, **config) -> dict:
    """The cell at a small size; ``config`` sets further keys of the
    configuration file (``attention_bias=False``, say)."""
    cfg = dict(spec.config(spec.workload(workload)["config"]), **config)
    kv = 4 if cfg["num_key_value_heads"] == cfg["num_attention_heads"] else 2
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=kv, num_hidden_layers=2, vocab_size=512,
               torch_dtype=dtype,
               serving={"kv_page": 4, "kv_topk_pages": 4, "kv_dtype": dtype})
    tr = dict(spec.traffic(spec.workload(workload)["traffic"]))
    if tr["loop"] == "closed":
        tr.update(workers=4, documents={"count": 2, "tokens": 48},
                  prompt={"dist": "uniform", "min": 3, "max": 9},
                  output={"dist": "pareto", "min": 4, "max": 16,
                          "alpha": 1.5},
                  warm_iterations=5)
        tr["engine"] = dict(tr["engine"], max_batch=4, chunk=16, pages=0)
    else:
        tr.update(prompt={"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 24},
                  output={"dist": "pareto", "min": 2, "max": 12,
                          "alpha": 0.5},
                  preroll_s=0.5, rate_per_s=20.0)
        tr["engine"] = dict(tr["engine"], max_batch=4, chunk=16)
    checks = {"sample_requests": 3, "min_served": 5,
              "logit_gap": {"limit": limit}, "logit_err": {"limit": limit}}
    return {"config": cfg, "traffic": tr, "checks": checks, "peaks": PEAKS}
