"""Plain float32 reference of a Qwen2-architecture decoder as served.

It computes, for one request, the logits at every position that produced
a served token, from the prompt and the served tokens alone.  It imports
nothing of the program.  The semantics it follows:

* token embedding; per layer RMSNorm (gain ``1 + w``: the weights are
  stored as offsets from 1), q/k/v projections with or without bias,
  rotary embedding (rotate-half, ``rope_theta``), attention, output
  projection, RMSNorm, gated MLP (SwiGLU, or GeGLU with tanh GELU);
  final RMSNorm and the tied or untied head;
* prompt positions attend densely and causally (chunked prefill);
* positions after the prompt are decode steps.  Their attention is the
  serving engine's TopK page attention: the context is cut into pages
  of ``kv_page`` tokens, each page is summarised by the mean of its keys
  up to the current position, every KV head scores each page by the
  largest dot product of its query heads with the summary, and
  attention runs over the tokens of the ``kv_topk_pages`` best pages.
  When the context has no more pages than that, every page is kept and
  the attention is exact.

Matrix products run at ``Precision.HIGHEST``.  With ``fp8=True`` the
operands of every matrix product (weights per output column,
activations per row) are rounded to float8 e4m3 first: the control that
must fail the comparison.  The computation goes layer by layer, with
queries in blocks, so that a context of several thousand tokens fits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
SEQ_PAD = 512          # sequences pad to a multiple of this
POS_PAD = 64           # logit rows pad to a multiple of this
F8_MAX = 448.0         # largest float8 e4m3 value


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    """x [..., K] @ w [K, N] in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x [S, H, D]; pos [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _attention(q, k, v, n_prompt, page, topk):
    """q [S, KV, G, D], k/v [S, KV, D] -> [S, KV, G, D]."""
    s_len, kv, g, d = q.shape
    n_pages = s_len // page
    kp = k.reshape(n_pages, page, kv, d)
    full_mean = kp.mean(axis=1)                              # [NP, KV, D]
    csum = jnp.cumsum(k, axis=0)                             # [S, KV, D]
    csum0 = jnp.concatenate([jnp.zeros((1, kv, d)), csum])   # [S+1, ...]
    tpos = jnp.arange(s_len)

    def block(args):
        qb, rows = args                                      # [B,KV,G,D], [B]
        cur = rows // page
        # frontier page: mean of its keys up to the row's position
        start = cur * page
        part = (csum0[rows + 1] - csum0[start]) / (
            (rows - start + 1).astype(jnp.float32)[:, None, None])
        s_full = jnp.einsum("bkgd,pkd->bkgp", qb, full_mean,
                            precision=HI).max(axis=2)        # [B,KV,NP]
        s_front = jnp.einsum("bkgd,bkd->bkg", qb, part,
                             precision=HI).max(axis=2)       # [B,KV]
        pid = jnp.arange(n_pages)
        sc = jnp.where(pid[None, None, :] == cur[:, None, None],
                       s_front[:, :, None], s_full)
        sc = jnp.where(pid[None, None, :] <= cur[:, None, None], sc, -jnp.inf)
        kth = jax.lax.top_k(sc, min(topk, n_pages))[0][..., -1:]  # [B,KV,1]
        keep = sc >= kth
        # prompt rows and short contexts attend to every page
        dense = (rows < n_prompt) | (cur + 1 <= topk)
        keep = keep | dense[:, None, None]
        tok_keep = jnp.repeat(keep, page, axis=-1)           # [B,KV,S]
        mask = tok_keep & (tpos[None, None, :] <= rows[:, None, None])
        sco = jnp.einsum("bkgd,tkd->bkgt", qb, k,
                         precision=HI) / (d ** 0.5)
        sco = jnp.where(mask[:, :, None, :], sco, -jnp.inf)
        w = jax.nn.softmax(sco, axis=-1)
        return jnp.einsum("bkgt,tkd->bkgd", w, v, precision=HI)

    nb = s_len // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, kv, g, d)
    rows = jnp.arange(s_len).reshape(nb, Q_BLOCK)
    return jax.lax.map(block, (qb, rows)).reshape(s_len, kv, g, d)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(shape_items, h, layers, li, n_prompt, fp8):
    s = dict(shape_items)
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, li, keepdims=False), layers)
    n, kv, d = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    s_len = h.shape[0]
    pos = jnp.arange(s_len)
    x = _rms(h, lp["ln1"], s["norm_eps"])
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v = (_mm(x, lp[w], fp8) for w in ("wq", "wk", "wv"))
    if s["qkv_bias"]:
        q, k, v = q + f32(lp["bq"]), k + f32(lp["bk"]), v + f32(lp["bv"])
    q, k, v = (q.reshape(s_len, n, d), k.reshape(s_len, kv, d),
               v.reshape(s_len, kv, d))
    q = _rope(q, pos, s["rope_theta"]).reshape(s_len, kv, n // kv, d)
    k = _rope(k, pos, s["rope_theta"])
    o = _attention(q, k, v, n_prompt, s["kv_page"], s["kv_topk_pages"])
    h = h + _mm(o.reshape(s_len, n * d), lp["wo"], fp8)
    x = _rms(h, lp["ln2"], s["norm_eps"])
    act = jax.nn.silu if s["act"] == "swiglu" else jax.nn.gelu
    up = act(_mm(x, lp["wg"], fp8)) * _mm(x, lp["wi"], fp8)
    return h + _mm(up, lp["wo_mlp"], fp8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(shape_items, h_rows, ln_f, head, fp8):
    s = dict(shape_items)
    return _mm(_rms(h_rows, ln_f, s["norm_eps"]), head, fp8)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(shape_items, embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


def logits(params: dict, shape: dict, prompt: np.ndarray,
           served: np.ndarray, fp8: bool = False) -> jax.Array:
    """Logits [len(served), vocab] at the positions that produced each
    served token: the last prompt position, then each decode step."""
    items = tuple(sorted(shape.items()))
    n_prompt, n_out = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    s_len = -(-len(seq) // SEQ_PAD) * SEQ_PAD
    seq = np.pad(seq, (0, s_len - len(seq)))
    h = _embed(items, params["embed"], jnp.asarray(seq))
    for li in range(shape["n_layers"]):
        h = _layer(items, h, params["layers"], np.int32(li),
                   np.int32(n_prompt), fp8)
    rows = n_prompt - 1 + np.arange(n_out)
    rows = np.pad(rows, (0, -(-n_out // POS_PAD) * POS_PAD - n_out))
    head = (params["embed"].T if shape["tie_embeddings"]
            else params["lm_head"])
    return _head(items, h[rows], params["ln_f"], head, fp8)[:n_out]


def compare(params: dict, shape: dict, prompt: np.ndarray,
            served: np.ndarray, control: bool = False) -> dict:
    """The reference's readings of one request.

    ``gap``: per served position, how far the reference's logit of the
    served token lies below the reference's best.  ``ends``: the
    reference's logits at the first and the last served position, the
    two rows the program keeps.  With ``control``, ``control_gap`` (the
    gap of the token the float8 control puts first) and
    ``control_ends`` (the control's logits at those two positions)."""
    ref = logits(params, shape, prompt, served)
    best = ref.max(axis=-1)
    idx = jnp.arange(len(served))
    ends = jnp.asarray([0, len(served) - 1])
    out = {"gap": np.asarray(best - ref[idx, jnp.asarray(served)]),
           "ends": np.asarray(ref[ends])}
    if control:
        ctl = logits(params, shape, prompt, served, fp8=True)
        out["control_gap"] = np.asarray(best - ref[idx, ctl.argmax(axis=-1)])
        out["control_ends"] = np.asarray(ctl[ends])
    return out
