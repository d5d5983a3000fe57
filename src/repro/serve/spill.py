"""Host-resident KV spill tier: the third level of the memory hierarchy.

The serve engine's pages live in three tiers (docs/MEMORY_HIERARCHY.md):
the NSB staging tail (hot, speculative copies), the HBM demand pool
(authoritative), and — this module — a **host spill pool** that holds
whole-page snapshots of preempted requests so preemption becomes
*swap-out* instead of free-and-recompute.

:class:`HostSpillPool` owns the host-side bytes only; slot *ids* are
allocated by :class:`~.kv_allocator.KVBlockAllocator` (so the
one-tier-per-page-id invariant is checkable in one place) and the engine
performs the actual device<->host copies when it drains the allocator's
transfer queues.  One slot stores one physical page across every layer
and plane — K, V, and the fp32 page summary the TopK selection reads —
so a swap-in restores not just attention content but the *selection*
behaviour byte-for-byte.

Beyond preemption, the tier doubles as the parking lot for **idle
multi-turn sessions** (``PagedEngine(session_hold=True, idle_swap=True)``):
when a conversation turn finishes, the engine adopts the request's block
table onto a holder rid and spills it here for the think-time gap, then
restores it — same snapshot/restore path, same strict drain order — when
the follow-up turn arrives carrying the conversation history.  Because
slots snapshot K, V, *and* the selection summaries exactly (uncompressed
tier), a resumed turn's prefix attach is byte-identical to a session
that was never swapped out; the allocator's ``session_rids`` accounting
distinguishes these parked pages from live-request spills.

Storage is pinned host memory by intent: arrays are committed to the
first CPU device via ``jax.device_put`` when a non-CPU backend is
present (so transfers are real host<->HBM DMAs), and plain numpy on a
CPU-only container where the distinction does not exist.  Either way
the pool never aliases device pool buffers.

Compression (``compress=True``) runs the spilled K/V planes through
``optim.compress.quantize_int8`` vmapped to **per-page, per-layer
scales** (one scale per (slot, layer, plane)): 2-byte KV dtypes spill at
~2x fewer host bytes, at the cost of bitwise resume — parity becomes
tolerance-bounded, with the worst-case absolute error of any restored
element ``scale/2`` per plane (asserted in tests/test_spill.py).  Page
summaries are always kept exact: they are tiny (one vector per page) and
keeping them exact keeps the post-resume TopK *selection* identical even
on the int8 tier.
"""

from __future__ import annotations

import numpy as np

from ..optim import compress as _compress


def _pin_host(x):
    """Commit ``x`` to host memory.  On accelerator backends this is a
    ``jax.device_put`` onto the first CPU device (pinned host staging
    buffer); on a CPU-only jax install the array is already host bytes
    and a plain numpy view avoids a pointless copy."""
    import jax
    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return np.asarray(x)
    if jax.default_backend() == "cpu":
        return np.asarray(x)
    return np.asarray(jax.device_put(x, cpu))


class HostSpillPool:
    """Fixed-slot host pool for spilled physical pages.

    Layout per slot (one physical page, all layers):

    * ``k``/``v``: ``[L, KV, page, D]`` in the pool dtype, or int8 with
      per-(slot, layer) scales when ``compress=True``;
    * ``s``: ``[L, KV, D]`` fp32 page summaries, always exact.

    The pool is indexed by *slot id*; the slot<->(request, logical page)
    bookkeeping lives in the allocator.  ``store``/``load`` operate on
    batches of slots so a whole swap lands in one vectorised call.
    """

    def __init__(self, n_slots: int, n_layers: int, page_tokens: int,
                 n_kv_heads: int, head_dim: int, dtype,
                 compress: bool = False) -> None:
        if n_slots < 1:
            raise ValueError(f"need >= 1 spill slot, got {n_slots}")
        self.n_slots = n_slots
        self.dtype = np.dtype(dtype)
        self.compress = bool(compress)
        shape = (n_slots, n_layers, n_kv_heads, page_tokens, head_dim)
        store_dt = np.int8 if self.compress else self.dtype
        self._k = np.zeros(shape, store_dt)
        self._v = np.zeros(shape, store_dt)
        self._s = np.zeros((n_slots, n_layers, n_kv_heads, head_dim),
                           np.float32)
        if self.compress:
            # per-page, per-layer, per-plane scales (k and v quantise
            # independently: their dynamic ranges differ per layer)
            self._scale_k = np.zeros((n_slots, n_layers), np.float32)
            self._scale_v = np.zeros((n_slots, n_layers), np.float32)

    # -- geometry ------------------------------------------------------------

    @property
    def host_bytes(self) -> int:
        """Resident host bytes of the pool (all slots, scales included)."""
        n = self._k.nbytes + self._v.nbytes + self._s.nbytes
        if self.compress:
            n += self._scale_k.nbytes + self._scale_v.nbytes
        return n

    def error_bound(self, slots) -> float:
        """Worst-case absolute dequantisation error over ``slots`` —
        half an int8 step of the largest per-page scale (0.0 when the
        pool is uncompressed: snapshots are bitwise)."""
        if not self.compress:
            return 0.0
        slots = np.asarray(list(slots), dtype=np.int64)
        if not slots.size:
            return 0.0
        return float(max(self._scale_k[slots].max(),
                         self._scale_v[slots].max()) / 2.0)

    # -- transfers -----------------------------------------------------------

    def _quantize(self, x: np.ndarray):
        """Per-(slot, layer) int8 quantisation via the shared
        ``optim.compress`` kernels (vmapped over the two leading axes so
        every page gets its own scale)."""
        import jax

        q, scale = jax.vmap(jax.vmap(_compress.quantize_int8))(
            np.asarray(x, np.float32))
        return np.asarray(q), np.asarray(scale, np.float32)

    def store(self, slots, k, v, s) -> None:
        """Write page snapshots into ``slots``.

        ``k``/``v`` are ``[n, L, KV, page, D]`` device-read bytes in the
        pool dtype, ``s`` is ``[n, L, KV, D]`` fp32; all are pinned to
        host before landing so the pool never holds device buffers."""
        slots = np.asarray(list(slots), dtype=np.int64)
        k = _pin_host(k)
        v = _pin_host(v)
        if self.compress:
            qk, sk = self._quantize(k)
            qv, sv = self._quantize(v)
            self._k[slots] = qk
            self._v[slots] = qv
            self._scale_k[slots] = sk
            self._scale_v[slots] = sv
        else:
            self._k[slots] = np.asarray(k, self.dtype)
            self._v[slots] = np.asarray(v, self.dtype)
        self._s[slots] = np.asarray(_pin_host(s), np.float32)

    def load(self, slots):
        """Read snapshots back: ``(k, v, s)`` with k/v dequantised to
        the pool dtype (bitwise-identical bytes when uncompressed)."""
        slots = np.asarray(list(slots), dtype=np.int64)
        if self.compress:
            import jax

            deq = jax.vmap(jax.vmap(_compress.dequantize_int8))
            k = np.asarray(deq(self._k[slots],
                               self._scale_k[slots])).astype(self.dtype)
            v = np.asarray(deq(self._v[slots],
                               self._scale_v[slots])).astype(self.dtype)
        else:
            k = self._k[slots]
            v = self._v[slots]
        return k, v, self._s[slots]
