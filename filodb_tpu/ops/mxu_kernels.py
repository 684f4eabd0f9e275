"""Regular-grid range kernels on the MXU.

When every staged series shares one timestamp vector (the overwhelmingly
common case for scraped metrics — one batch, one interval), the per-window
sample-membership and boundary-selection matrices are series-INDEPENDENT:

    sum_over_time  = vals @ W        W[t, j] = 1 if sample t in window j
    v_first        = vals @ F        F = one-hot of each window's first sample
    v_last         = vals @ L        L = one-hot of each window's last sample

i.e. the whole range-function evaluation becomes a handful of [S,T] x [T,J]
matmuls — exactly what the TPU MXU systolic array is built for — instead of
the gather/scatter-heavy general path (kernels.py), which this backend
executes orders of magnitude slower. The [T, J] matrices are built host-side
per query in O(T·J) (sub-millisecond) and cached on the staged block.

This is the TPU-first answer to the reference's chunked range functions
(rangefn/RangeFunction.scala:84): their per-chunk running aggregates exploit
chunk layout; we exploit the shared scrape grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .staging import StagedBlock

# functions the MXU path supports; everything else falls back to the
# general kernel
MXU_FUNCS = {
    "sum_over_time", "count_over_time", "avg_over_time", "last",
    "last_over_time", "first_over_time", "present_over_time",
    "absent_over_time", "timestamp", "stddev_over_time", "stdvar_over_time",
    "z_score", "rate", "increase", "delta", "idelta", "irate", "changes",
    "resets", "deriv", "predict_linear", "min_over_time", "max_over_time",
}

_TILE = 16  # tile width for the min/max hierarchy


def build_minmax_structures(lo, hi, T: int, J: int):
    """The ONE builder for the min/max window structure shared by the
    regular-grid and jittered-grid matrices: per-window full-_TILE tile
    masks plus the <=2*_TILE edge-sample one-hots/indices over the certain
    range [lo, hi) per window. Returns (tile_mask [J, T/_TILE],
    edge_onehot [T, J*2*_TILE], edge_valid [J, 2*_TILE], edge_idx i32)."""
    Lt = _TILE
    n_tiles = T // Lt
    t_lo = -(-lo // Lt)  # ceil
    t_hi = hi // Lt
    full = np.arange(n_tiles)[None, :]
    tile_mask = (
        (full >= t_lo[:, None]) & (full < t_hi[:, None]) & (t_lo < t_hi)[:, None]
    )
    E = np.zeros((T, J * 2 * Lt), dtype=np.float32)
    edge_valid = np.zeros((J, 2 * Lt), dtype=bool)
    edge_idx = np.zeros((J, 2 * Lt), dtype=np.int32)
    for j in range(J):
        if hi[j] <= lo[j]:
            continue
        if t_lo[j] >= t_hi[j]:  # window inside <2 tiles: all samples are edges
            left = np.arange(lo[j], hi[j])
            right = np.empty(0, dtype=np.int64)
        else:
            left = np.arange(lo[j], t_lo[j] * Lt)
            right = np.arange(t_hi[j] * Lt, hi[j])
        for slot, pos in enumerate(np.concatenate([left, right])[: 2 * Lt]):
            E[pos, j * 2 * Lt + slot] = 1.0
            edge_valid[j, slot] = True
            edge_idx[j, slot] = pos
    return tile_mask, E, edge_valid, edge_idx


def fetch_strategy(override: str | None = None) -> str:
    """Resolve the one-hot-selection fetch strategy for the MXU kernels.

    "matmul" fetches via one-hot matmuls (MXU-speed gathers on TPU);
    "gather" via jnp.take (~100x cheaper on the CPU backend); "auto" picks
    per backend at trace time. FILODB_MXU_FETCH forces a strategy globally —
    the parity test suite uses it to execute the TPU matmul path on CPU.
    The result is a static jit argument, so a forced run never reuses a
    cached auto-mode executable."""
    import os

    f = override or os.environ.get("FILODB_MXU_FETCH", "auto")
    if f not in ("auto", "matmul", "gather"):
        raise ValueError(f"bad fetch strategy {f!r}")
    return f


def use_gather_fetch(fetch: str, idx) -> bool:
    """Resolve a fetch strategy to a concrete choice at trace time (the one
    shared rule for all MXU kernels): gather when forced, or in auto mode on
    the CPU backend where jnp.take beats the one-hot matmul. A forced
    "gather" at a call site that supplies no gather indices is a miswiring —
    raise rather than silently compare the matmul path against itself."""
    if idx is None:
        if fetch == "gather":
            raise ValueError(
                "fetch='gather' forced but this call site provides no gather "
                "indices (idx=None)"
            )
        return False
    return fetch == "gather" or (
        fetch == "auto" and jax.default_backend() == "cpu"
    )


class WindowMatrices:
    """Host-precomputed per-(grid, window) matrices for one shared ts.

    ``put`` overrides the device placement of every device-resident copy
    (default: plain device_put). A series-sharded block passes a
    mesh-REPLICATED put so the matrices upload once with the placement the
    shard_map program wants — never a dead single-device copy."""

    def __init__(self, ts1: np.ndarray, n_valid: int, start_off: int, step_ms: int,
                 num_steps: int, window_ms: int, put=None):
        ts = ts1[:n_valid].astype(np.int64)
        T = len(ts1)
        J = num_steps
        out_t = start_off + np.arange(J, dtype=np.int64) * step_ms
        hi = np.searchsorted(ts, out_t, side="right")
        lo = np.searchsorted(ts, out_t - window_ms, side="right")
        cnt = (hi - lo).astype(np.float32)
        tidx = np.arange(T)[:, None]
        W = ((tidx >= lo[None, :]) & (tidx < hi[None, :])).astype(np.float32)
        F = np.zeros((T, J), dtype=np.float32)
        L = np.zeros((T, J), dtype=np.float32)
        L2 = np.zeros((T, J), dtype=np.float32)
        has = cnt > 0
        has2 = cnt >= 2
        F[lo[has], np.nonzero(has)[0]] = 1.0
        L[hi[has] - 1, np.nonzero(has)[0]] = 1.0
        L2[hi[has2] - 2, np.nonzero(has2)[0]] = 1.0
        pad = np.full(J, np.nan)
        self.W, self.F, self.L, self.L2 = W, F, L, L2
        self.count = cnt
        self.t_first = np.where(has, ts[np.minimum(lo, len(ts) - 1)], np.nan)
        self.t_last = np.where(has, ts[np.minimum(hi - 1, len(ts) - 1)], pad)
        self.t_last2 = np.where(has2, ts[np.clip(hi - 2, 0, len(ts) - 1)], pad)
        self.out_t = out_t.astype(np.float64)
        self.window_ms = window_ms
        self._ts1 = ts1
        self._lo, self._hi, self._T, self._J = lo, hi, T, J
        # gather-form of the one-hot selections for backends where a gather
        # beats a matmul (CPU; the TPU branch keeps the MXU one-hots):
        # row 0 = first-sample, 1 = last, 2 = second-to-last positions.
        # Out-of-range windows clip to valid positions; every use is gated
        # by has/count masks, matching the one-hot's all-zero columns.
        self.idx = np.stack([
            np.clip(lo, 0, T - 1),
            np.clip(hi - 1, 0, T - 1),
            np.clip(hi - 2, 0, T - 1),
        ]).astype(np.int32)
        # device-resident copies (transferred once, reused every query)
        import jax

        put = self._put = put if put is not None else jax.device_put
        self.dW, self.dF, self.dL, self.dL2 = map(put, (W, F, L, L2))
        self.d_count = put(cnt)
        self.d_tf = put(np.nan_to_num(self.t_first, nan=0.0).astype(np.float32))
        self.d_tl = put(np.nan_to_num(self.t_last, nan=0.0).astype(np.float32))
        self.d_tl2 = put(np.nan_to_num(self.t_last2, nan=0.0).astype(np.float32))
        self.d_out_t = put(self.out_t.astype(np.float32))
        self.d_idx = put(self.idx)
        # the heavyweight structures below (min/max edge one-hots ~ [T, 32J],
        # pair membership, regression moments) build LAZILY on first use:
        # sum/rate dashboards never pay for them, and live-edge append
        # repairs rebuild window matrices on every grid extension
        self._pairs_built = False
        self._minmax_built = False
        self._regression_built = False

    def ensure_pairs(self):
        """P: pair-membership for changes/resets (lazy)."""
        if self._pairs_built:
            return
        import jax

        tidx = np.arange(self._T)[:, None]
        P = ((tidx > self._lo[None, :]) & (tidx < self._hi[None, :])).astype(np.float32)
        self.P = P
        self.dP = self._put(P)
        self._pairs_built = True

    def ensure_regression(self):
        """Centered time moments for deriv/predict_linear (lazy)."""
        if self._regression_built:
            return
        import jax

        tc = (self._ts1.astype(np.float64)[:, None] - self.out_t[None, :]) * 1e-3
        self.Wt = (self.W * tc).astype(np.float32)
        self.st = self.Wt.sum(0)
        self.stt = (self.W * tc * tc).sum(0).astype(np.float64)
        self.dWt = self._put(self.Wt)
        self.d_st = self._put(self.st)
        self.d_stt = self._put(self.stt.astype(np.float32))
        self._regression_built = True

    def ensure_minmax(self):
        """min/max tile hierarchy + edge one-hots (lazy — the edge matrix is
        [T, 2*_TILE*J], by far the biggest structure here)."""
        if self._minmax_built:
            return
        import jax

        (self.tile_mask, self.edge_onehot, self.edge_valid,
         self.edge_idx) = build_minmax_structures(
            self._lo, self._hi, self._T, self._J
        )
        put = self._put
        self.d_tile_mask = put(self.tile_mask)
        self.d_edge_onehot = put(self.edge_onehot)
        self.d_edge_valid = put(self.edge_valid)
        self.d_edge_idx = put(self.edge_idx)
        self._minmax_built = True


def window_matrices(block: StagedBlock, start_off: int, step_ms: int,
                    num_steps: int, window_ms: int) -> WindowMatrices:
    """Per-(block, query-params) WindowMatrices, memoized on the block via
    the shared keyed single-flight (filodb_tpu/singleflight.memo_on): two
    racing same-key misses would each upload the full device-resident
    matrix set and the loser's copy would linger until GC. A series-sharded
    block (mesh superblock) uploads them REPLICATED across its mesh — the
    placement the shard_map program consumes, committed once at build."""
    from ..singleflight import memo_on
    from .staging import replicated_put

    key = (int(start_off), int(step_ms), int(num_steps), int(window_ms))
    mesh = getattr(block, "placement", None)
    return memo_on(
        block, "_wm_cache", key,
        lambda: WindowMatrices(block.regular_ts, int(block.lens[0]),
                               start_off, step_ms, num_steps, window_ms,
                               put=replicated_put(mesh) if mesh is not None
                               else None),
    )


@functools.partial(
    jax.jit, static_argnames=("func", "is_counter", "is_delta", "fetch")
)
@jax.named_scope("range_fn")
def mxu_range_kernel(
    func: str,
    vals,  # [S, T] f32
    raw,  # [S, T] f32 (counters; == vals otherwise)
    baseline,  # [S]
    W, F, L, L2,  # [T, J] f32
    count, t_first, t_last, t_last2,  # [J]
    out_t,  # [J] f64 ms
    window_ms,
    idx=None,  # [3, J] i32 first/last/last2 positions (CPU gather form)
    is_counter: bool = False,
    is_delta: bool = False,
    arg0=0.0,
    fetch: str = "auto",
):
    """Compute [S, J] results with matmuls on the MXU.

    The F/L/L2 one-hot matmuls are MXU-speed gathers on TPU; on the CPU
    backend a real gather (jnp.take with the idx rows) is ~100x cheaper, so
    the fetch strategy is chosen per backend at trace time. Gathered values
    at clipped positions are garbage exactly where the one-hot column is
    all-zero — both are discarded by the has/count gates."""
    f32 = jnp.float32
    has = count > 0
    w_s = window_ms.astype(f32) * 1e-3
    nan = jnp.nan

    def mm(x, M):
        return jax.lax.dot(x, M, precision=jax.lax.Precision.HIGHEST)

    if use_gather_fetch(fetch, idx):
        gF = lambda x: jnp.take(x, idx[0], axis=1)
        gL = lambda x: jnp.take(x, idx[1], axis=1)
        gL2 = lambda x: jnp.take(x, idx[2], axis=1)
    else:
        gF = lambda x: mm(x, F)
        gL = lambda x: mm(x, L)
        gL2 = lambda x: mm(x, L2)

    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        s = mm(vals, W)
        if func == "rate":
            s = s / w_s
        return jnp.where(has, s, nan)
    if func == "count_over_time":
        return jnp.where(has, count, nan)[None, :] * jnp.ones_like(vals[:, :1])
    if func == "avg_over_time":
        return jnp.where(has, mm(vals, W) / jnp.maximum(count, 1.0), nan)
    if func in ("last", "last_over_time"):
        return jnp.where(has, gL(vals), nan)
    if func == "first_over_time":
        return jnp.where(has, gF(vals), nan)
    if func == "present_over_time":
        return jnp.where(has, 1.0, nan)[None, :] * jnp.ones_like(vals[:, :1])
    if func == "absent_over_time":
        return jnp.where(has, nan, 1.0)[None, :] * jnp.ones_like(vals[:, :1])
    if func == "timestamp":
        return jnp.where(has, t_last.astype(f32), nan)[None, :] * jnp.ones_like(vals[:, :1])
    if func in ("stddev_over_time", "stdvar_over_time", "z_score"):
        s = mm(vals, W)
        s2 = mm(vals * vals, W)
        c = jnp.maximum(count, 1.0)
        mean = s / c
        var = jnp.maximum(s2 / c - mean * mean, 0.0)
        if func == "stdvar_over_time":
            return jnp.where(has, var, nan)
        sd = jnp.sqrt(var)
        if func == "stddev_over_time":
            return jnp.where(has, sd, nan)
        vl = gL(vals)
        return jnp.where(has, (vl - mean) / jnp.maximum(sd, 1e-30), nan)
    if func in ("rate", "increase", "delta"):
        vf = gF(vals)
        vl = gL(vals)
        dlt = vl - vf
        tf = t_first.astype(f32) * 1e-3
        tl = t_last.astype(f32) * 1e-3
        sampled = tl - tf
        range_start = (out_t.astype(f32) - window_ms.astype(f32)) * 1e-3
        range_end = out_t.astype(f32) * 1e-3
        dur_start = tf - range_start
        dur_end = range_end - tl
        avg_dur = sampled / jnp.maximum(count - 1.0, 1.0)
        thresh = avg_dur * 1.1
        if is_counter and func != "delta":
            v_first_raw = gF(raw)
            dur_zero = jnp.where(
                dlt > 0, sampled[None, :] * (v_first_raw / jnp.maximum(dlt, 1e-30)), jnp.inf
            )
            ds = jnp.minimum(dur_start[None, :], jnp.where(v_first_raw >= 0, dur_zero, jnp.inf))
        else:
            ds = jnp.broadcast_to(dur_start[None, :], dlt.shape)
        ds = jnp.where(ds >= thresh[None, :], (avg_dur / 2.0)[None, :], ds)
        de = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)[None, :]
        factor = (sampled[None, :] + ds + de) / jnp.maximum(sampled, 1e-30)[None, :]
        res = dlt * factor
        if func == "rate":
            res = res / w_s
        return jnp.where((count >= 2)[None, :], res, nan)
    if func in ("irate", "idelta"):
        ok = count >= 2
        if func == "idelta" and is_counter and not is_delta:
            # counter blocks arrive diff-encoded: last pair's diff via one-hot
            return jnp.where(ok[None, :], gL(vals), nan)
        vl = gL(vals)
        vp = gL2(vals)
        dt_s = (t_last - t_last2).astype(f32) * 1e-3
        dv = vl - vp
        r = dv / jnp.maximum(dt_s, 1e-30)[None, :] if func == "irate" else dv
        return jnp.where(ok[None, :], r, nan)
    raise ValueError(f"mxu kernel does not support {func}")


@functools.partial(jax.jit, static_argnames=())
def mxu_pair_count(flagged, P, has):
    """changes/resets: flagged [S,T] pair indicators @ P [T,J]."""
    n = jax.lax.dot(flagged, P, precision=jax.lax.Precision.HIGHEST)
    return jnp.where(has, n, jnp.nan)


@functools.partial(jax.jit, static_argnames=("n_valid", "is_min", "fetch"))
@jax.named_scope("range_fn")
def mxu_minmax(vals, tile_mask, edge_onehot, edge_valid, count,
               n_valid: int, is_min: bool = True, edge_idx=None,
               fetch: str = "auto"):
    """min/max_over_time on the regular grid: tile-hierarchy + edge samples
    via selection matmul (gathers are pathologically slow on the TPU
    backend; on CPU the gather form via edge_idx is far cheaper than the
    wide [T, J*2L] matmul). vals [S, T]; tile_mask [J, T/L];
    edge_onehot [T, J*2L]; edge_valid [J, 2L]; edge_idx [J, 2L] i32."""
    S, T = vals.shape
    L = _TILE
    J = tile_mask.shape[0]
    v = vals if is_min else -vals
    sentinel = jnp.float32(3e38)
    lane = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    vm = jnp.where(lane < n_valid, v, sentinel)
    tmin = vm.reshape(S, T // L, L).min(-1)  # [S, T/L]
    full = jnp.where(tile_mask[None, :, :], tmin[:, None, :], sentinel).min(-1)  # [S, J]
    if use_gather_fetch(fetch, edge_idx):
        edges = jnp.take(vm, edge_idx.reshape(-1), axis=1)
    else:
        edges = jax.lax.dot(vm, edge_onehot, precision=jax.lax.Precision.HIGHEST)
    edges = edges.reshape(S, J, 2 * L)
    edges = jnp.where(edge_valid[None, :, :], edges, sentinel).min(-1)  # [S, J]
    r = jnp.minimum(full, edges)
    r = r if is_min else -r
    return jnp.where((count > 0)[None, :], r, jnp.nan)


@functools.partial(jax.jit, static_argnames=("predict",))
def mxu_regression(vals, W, Wt, st, stt, count, has, lead, predict: bool = False):
    """deriv / predict_linear via least squares with host-precomputed
    time moments (tc centered at each window's out_t)."""
    sv = jax.lax.dot(vals, W, precision=jax.lax.Precision.HIGHEST)
    stv = jax.lax.dot(vals, Wt, precision=jax.lax.Precision.HIGHEST)
    n = count[None, :]
    denom = (n * stt[None, :] - (st * st)[None, :]).astype(jnp.float32)
    slope = (n * stv - st[None, :] * sv) / jnp.where(jnp.abs(denom) < 1e-30, 1.0, denom)
    ok = (count >= 2)[None, :] & (jnp.abs(denom) >= 1e-30)
    if not predict:
        return jnp.where(ok, slope, jnp.nan)
    intercept = (sv - slope * st[None, :]) / jnp.maximum(n, 1.0)
    return jnp.where(ok, intercept + slope * lead, jnp.nan)


def run_mxu_range_function(func, block: StagedBlock, params, is_counter=False,
                           is_delta=False, args=()):
    """Entry: dispatch one MXU-path range function. Caller guarantees
    block.regular_ts is set and func in MXU_FUNCS."""
    from .kernels import pad_steps

    J = pad_steps(params.num_steps)
    start_off = int(params.start_ms - block.base_ms)
    wm = window_matrices(block, start_off, params.step_ms, J, params.window_ms)
    if func in ("changes", "resets"):
        # must see raw value movement — corrected counter vals are monotone,
        # so resets()/changes() must not read them (kernels.py has the same
        # rule). Counter blocks arrive diff-encoded (staging mode "diff");
        # gauges compare raw values.
        wm.ensure_pairs()
        vals = jnp.asarray(block.raw if block.raw is not None else block.vals)
        if is_counter and not is_delta:
            flag = (vals != 0) if func == "changes" else (vals < 0)
        else:
            prev = jnp.concatenate([vals[:, :1], vals[:, :-1]], axis=1)
            flag = (vals != prev) if func == "changes" else (vals < prev)
        return mxu_pair_count(flag.astype(jnp.float32), wm.dP, wm.d_count > 0)
    if func in ("min_over_time", "max_over_time"):
        wm.ensure_minmax()
        return mxu_minmax(
            jnp.asarray(block.vals), wm.d_tile_mask, wm.d_edge_onehot,
            wm.d_edge_valid, wm.d_count,
            n_valid=int(block.lens[0]), is_min=(func == "min_over_time"),
            edge_idx=wm.d_edge_idx, fetch=fetch_strategy(),
        )
    if func in ("deriv", "predict_linear"):
        wm.ensure_regression()
        lead = np.float32(args[0]) if args else np.float32(0.0)
        return mxu_regression(
            block.vals, wm.dW, wm.dWt, wm.d_st, wm.d_stt,
            wm.d_count, wm.d_count > 0, lead,
            predict=(func == "predict_linear"),
        )
    raw = block.raw if block.raw is not None else block.vals
    return mxu_range_kernel(
        func,
        block.vals,
        raw,
        block.baseline,
        wm.dW, wm.dF, wm.dL, wm.dL2,
        wm.d_count,
        wm.d_tf,
        wm.d_tl,
        wm.d_tl2,
        wm.d_out_t,
        np.float32(params.window_ms),
        idx=wm.d_idx,
        is_counter=is_counter,
        is_delta=is_delta,
        fetch=fetch_strategy(),
    )


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.mxu_kernels",
        mxu_range_kernel=mxu_range_kernel,
        mxu_pair_count=mxu_pair_count,
        mxu_minmax=mxu_minmax,
        mxu_regression=mxu_regression,
    )


_register_kernel_observatory()
