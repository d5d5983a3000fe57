"""Logical-axis sharding: rules map logical axis names -> mesh axes.

Models annotate activations with ``constrain(x, "batch", None, "mlp")``;
outside a mesh context this is a no-op (CPU unit tests), inside
``jax.set_mesh`` it becomes ``with_sharding_constraint``.

Default rules implement DP("pod","data") x TP("model") with FSDP on the
"data" axis for large parameters and EP on "model" for divisible expert
counts (MaxText-style).
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

# logical name -> mesh axis (or tuple)
DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": ("data", "model"),   # long-context KV sequence sharding
    "heads": "model",
    "kv_heads": "model",
    "embed": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": "model",
    "moe_cap": "model",
    "seq_sp": "model",   # Megatron-style sequence parallelism between blocks
    "fsdp": ("pod", "data"),   # on multi-pod, params/opt shard across pods too
    "layers": None,
    "stage": "pod",                    # pipeline-parallel stage axis (opt-in)
}

_rules = dict(DEFAULT_RULES)


def set_rules(overrides: dict | None = None) -> None:
    global _rules
    _rules = dict(DEFAULT_RULES)
    if overrides:
        _rules.update(overrides)


def get_rules() -> dict:
    return dict(_rules)


def _mesh_axes() -> dict:
    """Auto axes of the current mesh, name -> size.  Manual axes (the
    body of a ``shard_map``) are left out: a sharding constraint there
    may name none of them, so ``constrain`` is a no-op inside one."""
    m = jax.sharding.get_abstract_mesh()
    return {name: size for (name, size), kind in zip(m.shape.items(),
                                                     m.axis_types)
            if kind != jax.sharding.AxisType.Manual}


def resolve(*logical, mesh_axes: dict | None = None) -> P:
    """Translate logical axis names to a PartitionSpec valid on the current
    mesh (silently dropping axes the mesh does not have)."""
    axes = _mesh_axes() if mesh_axes is None else mesh_axes
    out = []
    for name in logical:
        if name is None:
            out.append(None)
            continue
        ax = _rules.get(name, None)
        if ax is None:
            out.append(None)
            continue
        if isinstance(ax, tuple):
            present = tuple(a for a in ax if a in axes)
            out.append(present if present else None)
        else:
            out.append(ax if ax in axes else None)
    return P(*out)


def constrain(x: jax.Array, *logical) -> jax.Array:
    axes = _mesh_axes()
    if not axes:
        return x
    spec = resolve(*logical, mesh_axes=axes)
    # drop shardings that do not divide the dimension, and de-duplicate
    # mesh axes (first dim wins)
    fixed = []
    used: set = set()
    for dim, s in zip(x.shape, spec):
        if s:
            parts = (s,) if isinstance(s, str) else tuple(s)
            parts = tuple(a for a in parts if a not in used)
            s = (parts[0] if len(parts) == 1 else parts) if parts else None
        n = int(np.prod([axes[a] for a in ((s,) if isinstance(s, str) else s)])
                ) if s else 1
        ok = s if s and dim % n == 0 else None
        if ok:
            used.update((ok,) if isinstance(ok, str) else ok)
        fixed.append(ok)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


# -- parameter sharding rules --------------------------------------------------

# (regex on param path, logical spec per dim — trailing dims matched)
PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("vocab", "fsdp")),
    (r"lm_head$", ("fsdp", "vocab")),
    (r"\bwq$|\bwk$|\bwv$|\bwi$|\bwg$", ("fsdp", "heads")),
    (r"\bbq$|\bbk$|\bbv$", ("heads",)),
    (r"\bwo$|\bwo_mlp$", ("heads", "fsdp")),
    (r"\brouter$", ("fsdp", None)),
    (r"\bwe_gate$|\bwe_up$", ("experts", "fsdp", None)),
    (r"\bwe_down$", ("experts", None, "fsdp")),
    (r"\bln[0-9a-z_]*$|\bnorm[0-9a-z_]*$", (None,)),
    (r"\bw_rg.*$|\bconv.*$|\bwdt$|\bA_log$|\bD$|\bdt_bias$", (None,)),
]


def param_spec(path: str, shape: tuple, mesh_axes: dict,
               stacked: bool = False) -> P:
    """PartitionSpec for a parameter; leading layer axis (scan stack) is
    never sharded.  Falls back to a size-aware generic rule."""
    body = shape[1:] if stacked else shape
    logical: tuple | None = None
    for pat, spec in PARAM_RULES:
        if re.search(pat, path):
            logical = spec
            break
    if logical is not None and len(logical) == len(body):
        base = resolve(*logical, mesh_axes=mesh_axes)
    else:
        # generic: shard trailing dim on model if divisible, a big leading
        # dim on data (FSDP) if divisible
        spec = [None] * len(body)
        model = mesh_axes.get("model", 1)
        data = mesh_axes.get("data", 1)
        if body and model > 1 and body[-1] % model == 0 and body[-1] >= 512:
            spec[-1] = "model"
        for i, s in enumerate(body[:-1]):
            if data > 1 and s % data == 0 and s >= 1024:
                spec[i] = "data"
                break
        base = P(*spec)
    # drop shardings that do not divide
    fixed = []
    for dim, s in zip(body, base):
        n = 1
        if s:
            n = int(np.prod([mesh_axes[a]
                             for a in ((s,) if isinstance(s, str) else s)]))
        fixed.append(s if s and dim % n == 0 else None)
    # TP-rescue: if the model axis got dropped (e.g. grok: 8 experts on a
    # 16-way axis), recover it on the largest unsharded divisible dim so
    # huge tensors never end up 1D-sharded
    used = set()
    for s in fixed:
        for a in ((s,) if isinstance(s, str) else (s or ())):
            used.add(a)
    model = mesh_axes.get("model", 1)
    if model > 1 and "model" not in used:
        cands = [i for i, (dim, s) in enumerate(zip(body, fixed))
                 if s is None and dim % model == 0 and dim >= 512]
        if cands:
            best = max(cands, key=lambda i: body[i])
            fixed[best] = "model"
    if stacked:
        return P(None, *fixed)
    return P(*fixed)


# -- serve-layer tensor parallelism --------------------------------------------
#
# The paged serve engine shards along ONE axis only: the KV-head axis,
# over a 1-axis ("model",) mesh.  The sharded objects are the physical
# K/V/summary page pools and the QKV projection weights (head-sharded
# columns); *everything else* — output projection, FFN, norms, embed,
# lm_head — stays replicated, and per-head attention outputs are
# all-gathered before the output projection.  That asymmetry is
# deliberate: every cross-shard combine is a concatenation of
# independent per-head results, never an arithmetic reduction (no
# psum), so tp>1 logits are bitwise-identical to tp=1 — the serve
# layer's preemption-resume guarantee extended across shards.  (The
# training-path PARAM_RULES above shard FFN/vocab too and accept
# reduction-order drift; serving trades those FLOP savings for the
# bitwise invariant while keeping the KV pool — the memory-dominant
# object — at 1/tp bytes per shard.)

SERVE_TP_AXIS = "model"

_SERVE_TP_SHARDED = re.compile(r"\b(wq|wk|wv|bq|bk|bv)$")


def serve_pool_specs(axis: str = SERVE_TP_AXIS) -> tuple[P, P]:
    """(k/v pool spec, summary pool spec) for the paged engine's physical
    pools: ``[L, P, KV, page, D]`` and ``[L, P, KV, D]``, sharded on the
    KV-head dim only — the page-id dim is never sharded, so the
    allocator/scheduler/NVR-capture layers keep one global physical
    page-id space.  Written without trailing ``None``s, the form the
    outputs of ``shard_map`` carry, so a pool placed with these specs
    and one returned by a step hit the same jit cache entry."""
    return P(None, None, axis), P(None, None, axis)


def serve_param_specs(params, axis: str = SERVE_TP_AXIS):
    """PartitionSpec pytree for PagedEngine tensor parallelism.

    QKV projection weights/biases shard their trailing (flattened head)
    axis; every other leaf is fully replicated.  The flat head axis is
    head-major, so a 1/tp column slice is a contiguous block of whole
    GQA groups — consistent with the KV-head slice of the pools
    (requires ``n_heads % tp == 0 and n_kv_heads % tp == 0``).
    """
    def spec(path, leaf):
        name = "/".join(getattr(k, "key", str(k)) for k in path)
        if _SERVE_TP_SHARDED.search(name):
            nd = len(np.shape(leaf)) if not hasattr(leaf, "ndim") \
                else leaf.ndim
            return P(*([None] * (nd - 1)), axis)
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def constrain_like_params(tree, stacked_prefix: str = "layers"):
    """Constrain a params-shaped pytree (e.g. gradients) to the parameter
    sharding rules — turns gradient all-reduces into reduce-scatters on
    the FSDP axis (halves the per-layer gradient wire volume)."""
    axes = _mesh_axes()
    if not axes:
        return tree
    specs = tree_param_specs(tree, axes, stacked_prefix)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs,
        is_leaf=lambda x: hasattr(x, "shape"))


def tree_param_specs(params, mesh_axes: dict, stacked_prefix: str = "layers"):
    """Pytree of PartitionSpecs mirroring a params pytree."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    specs = {}
    for path, leaf in flat:
        keys = [getattr(k, "key", str(k)) for k in path]
        name = "/".join(keys)
        stacked = stacked_prefix in keys[:-1]
        shape = np.shape(leaf) if not hasattr(leaf, "shape") else leaf.shape
        specs[name] = param_spec(name, tuple(shape), mesh_axes, stacked)
    # rebuild tree
    def lookup(path, leaf):
        keys = "/".join(getattr(k, "key", str(k)) for k in path)
        return specs[keys]
    return jax.tree_util.tree_map_with_path(lookup, params)
