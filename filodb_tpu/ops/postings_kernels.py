"""Device-side posting-bitmap intersection (the part-key index's opt-in
HBM tier, memstore/index_device.py).

One tiny jit program: AND-reduce a stacked ``[M, W]`` array of packed
bitmap words — M staged posting bitmaps (one per equality matcher), W words
covering the shard's part-id universe. M is tiny (a selector rarely carries
more than ~6 matchers) so the reduction unrolls at trace time; the jit
cache keys on the (M, W) shape like every other kernel here.

Words are ``uint32`` on device: the host index packs ``uint64`` words, but
jax without ``jax_enable_x64`` silently narrows 64-bit integers, and
bitwise AND is invariant under the little-endian ``uint64 -> 2x uint32``
view reinterpretation, so the split is free and lossless both ways
(memstore/postings.py documents the bit-order contract).
"""

from __future__ import annotations

import numpy as np


def host_words_to_device(words: np.ndarray):
    """Pack host uint64 bitmap words for device residency (uint32 view)."""
    import jax

    return jax.device_put(np.ascontiguousarray(words).view(np.uint32))


def intersect_on_device(dev_words: list) -> np.ndarray:
    """AND the staged device bitmaps in ONE jit dispatch; returns the host
    uint64 result words. Instrumented like every other kernel entry point
    (per-dispatch latency + JIT hit/miss + the executable registry)."""
    import time as _time

    import jax.numpy as jnp

    from ..metrics import record_kernel_dispatch

    stacked = jnp.stack(dev_words)
    fn = _intersect_jit()
    t0 = _time.perf_counter()
    before = fn._cache_size()
    out_dev = fn(stacked)
    m_, w_ = stacked.shape
    record_kernel_dispatch(
        "postings_intersect", _time.perf_counter() - t0,
        compiled=fn._cache_size() > before,
        key={"variant": "general", "shapes": f"M{m_}xW{w_}"},
    )
    out = np.asarray(out_dev)
    return np.ascontiguousarray(out).view(np.uint64)


_jit_cache = {}


def _intersect_jit():
    import jax

    intersect_words = _jit_cache.get("intersect_words")
    if intersect_words is None:
        def _and_reduce(ws):
            out = ws[0]
            # static leading dim: unrolled at trace time, ONE fused kernel
            for i in range(1, ws.shape[0]):
                out = out & ws[i]
            return out

        intersect_words = _jit_cache["intersect_words"] = jax.jit(_and_reduce)
        from ..obs.kernels import KERNELS

        KERNELS.register_jits("ops.postings_kernels",
                              intersect_words=intersect_words)
    return intersect_words
