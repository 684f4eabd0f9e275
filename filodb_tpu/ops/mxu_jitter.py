"""Near-regular (jittered) grid range kernels on the MXU.

Real Prometheus scrape timestamps jitter around the scrape interval; the
exact-shared-grid MXU path (mxu_kernels.py) requires identical timestamps
across series, so jittered data used to drop to the ~40x-slower gather path.
This module keeps it on the MXU with EXACT semantics (the window-membership
contract of the reference's window iterators, PeriodicSamplesMapper.scala:256):

Staging detects blocks where every series has the same sample count and each
sample lies within half a nominal interval of a shared nominal grid
(staging.StagedBlock.nominal_ts / ts_dev / maxdev_ms). Then for any window
boundary at most ONE nominal slot has per-series-uncertain membership:

- slots with nominal time in (b + maxdev, e - maxdev] are in the window for
  EVERY series -> one shared certain-membership matrix W0 (an MXU matmul);
- the <=1 uncertain slot per boundary (klo at the lower edge, khi at the
  upper) is resolved per series from the staged deviations: its value/time
  is fetched with a one-hot MATMUL (an MXU-speed gather) and its membership
  is an elementwise compare of the deviation against the boundary offset.

So sum/count/first/last/rate/... become `certain part (shared matmul) +
per-series boundary corrections (elementwise)`, and the whole evaluation
stays matmul-dominated. Precision: boundary times are computed RELATIVE to
each window's start in f32 ms (exact below ~4.6h windows; beyond that the
sub-10ms rounding is far inside the oracle tolerance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .staging import StagedBlock

# supported under jitter; everything else falls back to the general kernels
JITTER_FUNCS = {
    "sum_over_time", "count_over_time", "avg_over_time", "last",
    "last_over_time", "first_over_time", "present_over_time",
    "absent_over_time", "stddev_over_time", "stdvar_over_time", "z_score",
    "rate", "increase", "delta", "idelta", "irate",
    "min_over_time", "max_over_time",
}

_TILE = 16  # tile width for the min/max hierarchy (matches mxu_kernels)


class JitterWindowMatrices:
    """Host-precomputed certain/uncertain window structure for one
    (nominal grid, output grid, window) triple."""

    def __init__(self, nominal_ts: np.ndarray, n_valid: int, maxdev_ms: int,
                 start_off: int, step_ms: int, num_steps: int, window_ms: int,
                 put=None):
        R = nominal_ts[:n_valid].astype(np.int64)
        T = len(nominal_ts)
        J = num_steps
        m = n_valid
        out_t = start_off + np.arange(J, dtype=np.int64) * step_ms
        b = out_t - window_ms
        e = out_t
        md = int(maxdev_ms)
        # klo == khi (a single sample uncertain at BOTH boundaries) is only
        # possible for windows not wider than the deviation band; caller
        # falls back to the general path
        self.ok = window_ms > 2 * md
        if not self.ok:
            return
        clo = np.searchsorted(R, b + md, side="right")
        chi = np.searchsorted(R, e - md, side="right")
        count0 = np.maximum(chi - clo, 0)
        klo_a = np.searchsorted(R, b - md, side="right")
        klo_b = np.searchsorted(R, b + md, side="right")
        khi_a = np.searchsorted(R, e - md, side="right")
        khi_b = np.searchsorted(R, e + md, side="right")
        # staging guarantees 2*maxdev < min nominal interval, so each
        # boundary band contains at most one slot
        has_klo = (klo_b - klo_a) == 1
        has_khi = (khi_b - khi_a) == 1
        klo = np.where(has_klo, klo_a, 0)
        khi = np.where(has_khi, khi_a, 0)
        chi = np.minimum(chi, m)
        c0pos = count0 > 0
        c0ge2 = count0 >= 2

        tidx = np.arange(T)[:, None]
        W0 = ((tidx >= clo[None, :]) & (tidx < chi[None, :])).astype(np.float32)

        def onehot(idx, mask):
            M = np.zeros((T, J), dtype=np.float32)
            cols = np.nonzero(mask)[0]
            M[idx[cols], cols] = 1.0
            return M

        F0 = onehot(clo, c0pos)
        L0 = onehot(chi - 1, c0pos)
        L2 = onehot(chi - 2, c0ge2)
        Klo = onehot(klo, has_klo)
        Khi = onehot(khi, has_khi)
        # certain-membership matrix: the ONE matmul every sum-family function
        # needs (same cost class as the regular-grid path's W)
        self.W0 = W0
        # the five boundary/edge selections, in BOTH fetch forms: stacked
        # one-hots for an MXU matmul (TPU), and gather indices for jnp.take
        # (CPU, where a take is ~100x cheaper than the matmul). The kernel
        # slices out only the rows the requested function needs, so e.g.
        # rate never pays for an L2 fetch and count pays for no vals fetch
        # at all. Clipped positions yield garbage exactly where the one-hot
        # column is all-zero — every use is gated by the c0pos/has_* masks.
        self.SEL = np.stack([F0, L0, L2, Klo, Khi], axis=1).reshape(T, 5 * J)
        self.idx = np.stack([
            np.clip(clo, 0, T - 1),
            np.clip(chi - 1, 0, T - 1),
            np.clip(chi - 2, 0, T - 1),
            np.clip(klo, 0, T - 1),
            np.clip(khi, 0, T - 1),
        ]).astype(np.int32)

        def rel(idx, mask):
            """nominal time of slot idx relative to each window's start b."""
            r = R[np.clip(idx, 0, m - 1)] - b
            return np.where(mask, r, 0).astype(np.float32)

        self.count0 = count0.astype(np.float32)
        self.c0pos = c0pos
        self.c0ge2 = c0ge2
        self.has_klo = has_klo
        self.has_khi = has_khi
        self.F0_rel = rel(clo, c0pos)
        self.L0_rel = rel(chi - 1, c0pos)
        self.L2_rel = rel(chi - 2, c0ge2)
        self.Klo_rel = rel(klo, has_klo)
        self.Khi_rel = rel(khi, has_khi)
        # membership thresholds for the uncertain slots, as deviation bounds:
        # klo in window  <=>  ts > b  <=>  dev > b - R[klo]
        # khi in window  <=>  ts <= e <=>  dev <= e - R[khi]
        self.blo_rel = np.where(
            has_klo, b - R[np.clip(klo, 0, m - 1)], 2 * md + 1
        ).astype(np.float32)
        self.ehi_rel = np.where(
            has_khi, e - R[np.clip(khi, 0, m - 1)], -(2 * md) - 1
        ).astype(np.float32)

        # certain-range boundary indices in plain [J] form: the histogram
        # jitter variant fetches [S, J, B] rows at these SHARED indices
        # (jnp.take along T) instead of building [T, J] one-hots per bucket
        self.clo = np.clip(clo, 0, T).astype(np.int32)
        self.chi = np.clip(chi, 0, T).astype(np.int32)

        # min/max tile hierarchy + edge one-hots build LAZILY (the edge
        # matrix is [T, 2*_TILE*J] — by far the biggest structure here, and
        # only min/max_over_time reads it)
        self._clo, self._chi, self._T, self._J = clo, chi, T, J
        self._minmax_built = False

        put = self._put = put if put is not None else jax.device_put
        self.d_clo = put(self.clo)
        self.d_chi = put(self.chi)
        self.d_W0 = put(self.W0)
        self.d_SEL = put(self.SEL)
        self.d_count0 = put(self.count0)
        self.d_c0pos = put(self.c0pos)
        self.d_c0ge2 = put(self.c0ge2)
        self.d_has_klo = put(self.has_klo)
        self.d_has_khi = put(self.has_khi)
        self.d_F0_rel = put(self.F0_rel)
        self.d_L0_rel = put(self.L0_rel)
        self.d_L2_rel = put(self.L2_rel)
        self.d_Klo_rel = put(self.Klo_rel)
        self.d_Khi_rel = put(self.Khi_rel)
        self.d_blo_rel = put(self.blo_rel)
        self.d_ehi_rel = put(self.ehi_rel)
        self.d_idx = put(self.idx)

    def ensure_minmax(self):
        """min/max tile hierarchy over the certain range [clo, chi) plus
        the <=2*_TILE edge-sample selections (lazy; shared builder with the
        regular-grid matrices)."""
        if self._minmax_built:
            return
        from .mxu_kernels import build_minmax_structures

        (self.tile_mask, self.edge_onehot, self.edge_valid,
         self.edge_idx) = build_minmax_structures(
            self._clo, self._chi, self._T, self._J
        )
        put = self._put
        self.d_tile_mask = put(self.tile_mask)
        self.d_edge_onehot = put(self.edge_onehot)
        self.d_edge_valid = put(self.edge_valid)
        self.d_edge_idx = put(self.edge_idx)
        self._minmax_built = True


def _cached_window_matrices(block, cache_attr: str, nominal_ts, n_valid: int,
                            maxdev_ms: int, start_off: int, step_ms: int,
                            num_steps: int, window_ms: int) -> JitterWindowMatrices:
    """One per-block memoization discipline for both the aligned-jitter and
    masked grid sources (keyed on the query window parameters), via the
    shared keyed single-flight so racing builders construct once. A
    series-sharded block (mesh superblock) uploads the matrices REPLICATED
    across its mesh — the placement the shard_map fused program consumes,
    committed once at build (same contract as mxu_kernels.window_matrices)."""
    from ..singleflight import memo_on
    from .staging import replicated_put

    mesh = getattr(block, "placement", None)
    key = (int(start_off), int(step_ms), int(num_steps), int(window_ms))
    return memo_on(
        block, cache_attr, key,
        lambda: JitterWindowMatrices(
            np.asarray(nominal_ts), n_valid, maxdev_ms,
            start_off, step_ms, num_steps, window_ms,
            put=replicated_put(mesh) if mesh is not None else None,
        ),
    )


def jitter_window_matrices(block: StagedBlock, start_off: int, step_ms: int,
                           num_steps: int, window_ms: int) -> JitterWindowMatrices:
    return _cached_window_matrices(
        block, "_jwm_cache", block.nominal_ts, int(np.asarray(block.lens)[0]),
        block.maxdev_ms, start_off, step_ms, num_steps, window_ms,
    )


# rows of SEL / idx, by name
_F0, _L0, _L2, _KLO, _KHI = range(5)


@functools.partial(
    jax.jit, static_argnames=("func", "is_counter", "is_delta", "fetch")
)
@jax.named_scope("range_fn")
def jitter_range_kernel(
    func: str,
    vals,  # [S, T] f32
    dev,  # [S, T] f32 per-sample deviation from the nominal grid (ms)
    raw,  # [S, T] f32 (counters; == vals otherwise)
    W0,  # [T, J] certain-membership matrix
    SEL,  # [T, 5J]: F0|L0|L2|Klo|Khi one-hot stack
    idx,  # [5, J] i32 gather-form of the same selections (or None)
    count0, c0pos, c0ge2, has_klo, has_khi,  # [J]
    F0_rel, L0_rel, L2_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,  # [J] f32
    window_ms,
    is_counter: bool = False,
    is_delta: bool = False,
    fetch: str = "auto",
):
    """Each branch fetches ONLY the selections it needs — the certain-window
    matmul (x @ W0) is paid only by the sum family, and rate/irate reduce to
    a handful of one-hot fetches + elementwise math, the same cost class as
    the regular-grid kernel. ``fetch`` picks the selection strategy: "matmul"
    (MXU one-hots), "gather" (jnp.take — far cheaper on CPU), or "auto"
    (backend-chosen at trace time)."""
    f32 = jnp.float32
    nan = jnp.nan
    from .mxu_kernels import use_gather_fetch

    S, T = vals.shape
    J = W0.shape[1]
    use_gather = use_gather_fetch(fetch, idx)
    # gather mode: ONE five-row gather per source plane, memoized at trace
    # time — XLA's CPU gather streams the source plane per op, so two
    # gathers of different rows from one plane cost two plane passes while
    # the full [5, J] index set costs barely more than either (5*S*J
    # fetched vs the S*T plane read). Branches slice the rows they need;
    # values are bit-identical to per-row gathers.
    _planes: dict = {}

    def sel(x, rows):
        """Fetch the named selection rows of x as [S, len(rows), J]."""
        r = np.array(rows)
        if use_gather:
            full = _planes.get(id(x))
            if full is None:
                full = jnp.take(x, idx.reshape(-1), axis=1).reshape(S, 5, J)
                _planes[id(x)] = full
            return full[:, r, :]
        M = SEL.reshape(T, 5, J)[:, r, :].reshape(T, len(rows) * J)
        a = jax.lax.dot(x, M, precision=jax.lax.Precision.HIGHEST)
        return a.reshape(S, len(rows), J)

    def mmW0(x):
        return jax.lax.dot(x, W0, precision=jax.lax.Precision.HIGHEST)

    # boundary membership: needed by every function
    dKlo, dKhi = (a for a in sel(dev, (_KLO, _KHI)).swapaxes(0, 1))
    in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :])
    in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :])
    cnt = count0[None, :] + in_lo + in_hi
    has = cnt > 0
    w_s = window_ms.astype(f32) * 1e-3

    def w3(m1, a, m2, b_, c):
        return jnp.where(m1, a, jnp.where(m2, b_, c))

    # the one definition of the ordered last-sample selection rule
    # ([klo?] certain[clo..chi) [khi?]); first/prev variants stay inline at
    # their single use sites
    def vlast(vL0, vKlo, vKhi):
        return w3(in_hi, vKhi, c0pos[None, :], vL0, vKlo)

    def tlast(dL0):
        return w3(in_hi, Khi_rel[None, :] + dKhi, c0pos[None, :],
                  L0_rel[None, :] + dL0, Klo_rel[None, :] + dKlo)

    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        if func == "rate":
            s = s / w_s
        return jnp.where(has, s, nan)
    if func == "count_over_time":
        return jnp.where(has, cnt, nan)
    if func == "avg_over_time":
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        return jnp.where(has, s / jnp.maximum(cnt, 1.0), nan)
    if func == "present_over_time":
        return jnp.where(has, 1.0, nan)
    if func == "absent_over_time":
        return jnp.where(has, nan, 1.0)
    if func in ("stddev_over_time", "stdvar_over_time", "z_score"):
        if func == "z_score":
            vL0, vKlo, vKhi = (
                a for a in sel(vals, (_L0, _KLO, _KHI)).swapaxes(0, 1)
            )
        else:
            vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        s2 = (
            mmW0(vals * vals)
            + jnp.where(in_lo, vKlo * vKlo, 0.0)
            + jnp.where(in_hi, vKhi * vKhi, 0.0)
        )
        c = jnp.maximum(cnt, 1.0)
        mean = s / c
        var = jnp.maximum(s2 / c - mean * mean, 0.0)
        if func == "stdvar_over_time":
            return jnp.where(has, var, nan)
        sd = jnp.sqrt(var)
        if func == "stddev_over_time":
            return jnp.where(has, sd, nan)
        return jnp.where(
            has, (vlast(vL0, vKlo, vKhi) - mean) / jnp.maximum(sd, 1e-30), nan
        )

    # ordered in-window sample selection: [klo?] certain[clo..chi) [khi?]
    if func == "first_over_time":
        vF0, vKlo, vKhi = (
            a for a in sel(vals, (_F0, _KLO, _KHI)).swapaxes(0, 1)
        )
        return jnp.where(has, w3(in_lo, vKlo, c0pos[None, :], vF0, vKhi), nan)
    if func in ("last", "last_over_time"):
        vL0, vKlo, vKhi = (
            a for a in sel(vals, (_L0, _KLO, _KHI)).swapaxes(0, 1)
        )
        return jnp.where(has, vlast(vL0, vKlo, vKhi), nan)
    if func in ("rate", "increase", "delta"):
        vF0, vL0, vKlo, vKhi = (
            a for a in sel(vals, (_F0, _L0, _KLO, _KHI)).swapaxes(0, 1)
        )
        dF0, dL0 = (a for a in sel(dev, (_F0, _L0)).swapaxes(0, 1))
        v_first = w3(in_lo, vKlo, c0pos[None, :], vF0, vKhi)
        v_last = vlast(vL0, vKlo, vKhi)
        tf_rel = w3(in_lo, Klo_rel[None, :] + dKlo, c0pos[None, :],
                    F0_rel[None, :] + dF0, Khi_rel[None, :] + dKhi)
        tl_rel = tlast(dL0)
        dlt = v_last - v_first
        sampled = (tl_rel - tf_rel) * 1e-3
        dur_start = tf_rel * 1e-3
        dur_end = (window_ms.astype(f32) - tl_rel) * 1e-3
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        if is_counter and func != "delta":
            rF0, rKlo, rKhi = (
                a for a in sel(raw, (_F0, _KLO, _KHI)).swapaxes(0, 1)
            )
            v_first_raw = w3(in_lo, rKlo, c0pos[None, :], rF0, rKhi)
            dur_zero = jnp.where(
                dlt > 0, sampled * (v_first_raw / jnp.maximum(dlt, 1e-30)), jnp.inf
            )
            ds = jnp.minimum(dur_start, jnp.where(v_first_raw >= 0, dur_zero, jnp.inf))
        else:
            ds = dur_start
        ds = jnp.where(ds >= thresh, avg_dur / 2.0, ds)
        de = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + ds + de) / jnp.maximum(sampled, 1e-30)
        res = dlt * factor
        if func == "rate":
            res = res / w_s
        return jnp.where(cnt >= 2, res, nan)
    if func in ("irate", "idelta"):
        ok2 = cnt >= 2
        if func == "idelta" and is_counter and not is_delta:
            # diff-encoded counters: the staged value AT the last in-window
            # sample is already the f64-exact last-pair difference
            vL0, vKlo, vKhi = (
                a for a in sel(vals, (_L0, _KLO, _KHI)).swapaxes(0, 1)
            )
            return jnp.where(ok2, vlast(vL0, vKlo, vKhi), nan)
        vL0, vL2, vKlo, vKhi = (
            a for a in sel(vals, (_L0, _L2, _KLO, _KHI)).swapaxes(0, 1)
        )
        v_last = vlast(vL0, vKlo, vKhi)
        dL0, dL2 = (a for a in sel(dev, (_L0, _L2)).swapaxes(0, 1))
        tl_rel = tlast(dL0)
        v_prev = jnp.where(
            in_hi,
            jnp.where(c0pos[None, :], vL0, vKlo),
            jnp.where(c0ge2[None, :], vL2, vKlo),
        )
        tp_rel = jnp.where(
            in_hi,
            jnp.where(c0pos[None, :], L0_rel[None, :] + dL0, Klo_rel[None, :] + dKlo),
            jnp.where(c0ge2[None, :], L2_rel[None, :] + dL2, Klo_rel[None, :] + dKlo),
        )
        dt_s = (tl_rel - tp_rel) * 1e-3
        dv = v_last - v_prev
        r = dv / jnp.maximum(dt_s, 1e-30) if func == "irate" else dv
        return jnp.where(ok2, r, nan)
    raise ValueError(f"jitter kernel does not support {func}")


@functools.partial(jax.jit, static_argnames=("n_valid", "is_min", "fetch"))
@jax.named_scope("range_fn")
def jitter_minmax(vals, dev, SEL, idx, tile_mask, edge_onehot, edge_valid,
                  edge_idx, count0, has_klo, has_khi, blo_rel, ehi_rel,
                  n_valid: int, is_min: bool = True, fetch: str = "auto"):
    """min/max over the certain range via the tile hierarchy + edge one-hots
    (mxu_kernels.mxu_minmax structure), then fold in the <=2 per-series
    uncertain boundary samples. ``fetch`` as in jitter_range_kernel."""
    from .mxu_kernels import use_gather_fetch

    S, T = vals.shape
    Lt = _TILE
    J = tile_mask.shape[0]
    use_gather = use_gather_fetch(fetch, idx)
    v = vals if is_min else -vals
    sentinel = jnp.float32(3e38)
    lane = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    vm = jnp.where(lane < n_valid, v, sentinel)
    tmin = vm.reshape(S, T // Lt, Lt).min(-1)
    certain = jnp.where(tile_mask[None, :, :], tmin[:, None, :], sentinel).min(-1)
    if use_gather and edge_idx is not None:
        edges = jnp.take(vm, edge_idx.reshape(-1), axis=1)
    else:
        edges = jax.lax.dot(vm, edge_onehot, precision=jax.lax.Precision.HIGHEST)
    edges = edges.reshape(S, J, 2 * Lt)
    edges = jnp.where(edge_valid[None, :, :], edges, sentinel).min(-1)
    r = jnp.minimum(certain, edges)

    def sel_kk(x):
        if use_gather:
            return jnp.take(x, idx[3:5].reshape(-1), axis=1).reshape(S, 2, J)
        M = SEL.reshape(T, 5, J)[:, 3:5, :].reshape(T, 2 * J)
        return jax.lax.dot(
            x, M, precision=jax.lax.Precision.HIGHEST
        ).reshape(S, 2, J)

    A = sel_kk(v)
    vKlo, vKhi = A[:, 0, :], A[:, 1, :]
    D = sel_kk(dev)
    dKlo, dKhi = D[:, 0, :], D[:, 1, :]
    in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :])
    in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :])
    r = jnp.minimum(r, jnp.where(in_lo, vKlo, sentinel))
    r = jnp.minimum(r, jnp.where(in_hi, vKhi, sentinel))
    cnt = count0[None, :] + in_lo + in_hi
    r = r if is_min else -r
    return jnp.where(cnt > 0, r, jnp.nan)


@functools.partial(
    jax.jit, static_argnames=("func", "is_counter", "is_delta", "fetch")
)
@jax.named_scope("range_fn")
def jitter_masked_kernel(
    func: str,
    vals,  # [S, T] f32 slot-aligned, 0 at holes
    dev,  # [S, T] f32 deviation from nominal, 0 at holes
    raw,  # [S, T] f32 raw (counters; == vals otherwise)
    valid,  # [S, T] f32 1.0 = real sample
    cc,  # [S, T] f32 cumulative valid count
    ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw,  # [S, T] host-precomputed fills
    W0,  # [T, J]
    SEL,  # [T, 5J]
    idx,  # [5, J] i32 or None
    c0pos_g,  # [J] bool: grid-level certain range non-empty
    has_klo, has_khi,  # [J] bool
    F0_rel, L0_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,  # [J] f32
    window_ms,
    is_counter: bool = False,
    is_delta: bool = False,
    fetch: str = "auto",
    maxdev=None,
):
    """Missing-scrape variant of jitter_range_kernel: per-slot validity masks
    replace the equal-count assumption. Per-series window counts come from
    the validity prefix sum (cc[chi-1] - cc[clo] + valid[clo], shared-index
    fetches — no extra matmul), and first/last selections read the
    host-precomputed forward/backward fills at SHARED slot indices — so a
    dropped scrape costs a few fetches, not a fall to the general path.
    Same window-semantics contract: PeriodicSamplesMapper.scala:256.

    With ``maxdev`` (the grid's maxdev_ms) the GATHER mode runs a LEAN
    fetch plan exploiting the time-fill invariant (staging.masked_fills):
    at a valid slot ffd == bfd == dev (|.| <= maxdev) while a hole pushes
    ffd below -maxdev and bfd above it, so boundary membership, slot
    validity and the boundary values all come from the fill planes — no
    validity fetches at all, and the hot counter-rate path drops from 11
    gather ops over 16 rows to 6 ops over 14 rows. Selected values are bit-identical to the classic plan
    (fills COPY the staged values at valid slots), so gather-vs-matmul
    parity is preserved; gathers on the CPU backend are the dominant cost
    of this kernel, which is what the jitter+holes bench ratio gates."""
    from .mxu_kernels import use_gather_fetch

    f32 = jnp.float32
    nan = jnp.nan
    S, T = vals.shape
    J = W0.shape[1]
    use_gather = use_gather_fetch(fetch, idx)
    lean = use_gather and maxdev is not None
    # exact-row gather memo: gathers dominate this kernel's cost on CPU
    # (roughly linear in fetched rows, with a per-op floor), so identical
    # (plane, rows) fetches dedup at trace time and the LEAN plan below
    # fetches each plane's row UNION once
    _memo: dict = {}

    def sel(x, rows):
        r = np.array(rows)
        if use_gather:
            key = (id(x), tuple(rows))
            got = _memo.get(key)
            if got is None:
                got = jnp.take(x, idx[r].reshape(-1), axis=1).reshape(
                    S, len(rows), J)
                _memo[key] = got
            return got
        M = SEL.reshape(T, 5, J)[:, r, :].reshape(T, len(rows) * J)
        a = jax.lax.dot(x, M, precision=jax.lax.Precision.HIGHEST)
        return a.reshape(S, len(rows), J)

    def mmW0(x):
        return jax.lax.dot(x, W0, precision=jax.lax.Precision.HIGHEST)

    if lean:
        # membership + validity from the time fills alone: ffd@klo is dev
        # at a valid klo and <= -(interval - maxdev) < blo_rel at a hole
        # (symmetrically bfd@khi vs ehi_rel), and |ffd@clo| <= maxdev is
        # exactly valid[clo]. Fetch each plane's full row union here —
        # the rate family reuses ffd@L0 / bfd@F0 for its window-edge
        # times, and the sel memo makes the reuse free
        Fd = sel(ffd, (_F0, _L0, _KLO))
        ffdF0, ffdL0, dKlo = Fd[:, 0, :], Fd[:, 1, :], Fd[:, 2, :]
        Bd = sel(bfd, (_F0, _KHI))
        bfdF0, dKhi = Bd[:, 0, :], Bd[:, 1, :]
        in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :])
        in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :])
        vaF0 = jnp.where(jnp.abs(ffdF0) <= maxdev, f32(1.0), f32(0.0))
    else:
        dKlo, dKhi = (a for a in sel(dev, (_KLO, _KHI)).swapaxes(0, 1))
        vaKlo, vaKhi = (a for a in sel(valid, (_KLO, _KHI)).swapaxes(0, 1))
        in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :]) & (vaKlo > 0)
        in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :]) & (vaKhi > 0)
        vaF0 = sel(valid, (_F0,))[:, 0, :]
    # per-series certain-range sample count from the validity prefix sum:
    # count over [clo, chi) = cc[chi-1] - cc[clo] + valid[clo]; the gather
    # form reads clipped garbage where the grid's certain range is empty, so
    # gate on the grid-level c0pos (the matmul's zero columns do the same)
    ccF0, ccL0 = (a for a in sel(cc, (_F0, _L0)).swapaxes(0, 1))
    cnt0v = jnp.where(c0pos_g[None, :], ccL0 - ccF0 + vaF0, 0.0)
    cnt = cnt0v + in_lo + in_hi
    has = cnt > 0
    c0pos = cnt0v > 0
    c0ge2 = cnt0v >= 2
    w_s = window_ms.astype(f32) * 1e-3

    def w3(m1, a, m2, b_, c):
        return jnp.where(m1, a, jnp.where(m2, b_, c))

    def vlast(vL0f, vKlo, vKhi):
        return w3(in_hi, vKhi, c0pos, vL0f, vKlo)

    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        if func == "rate":
            s = s / w_s
        return jnp.where(has, s, nan)
    if func == "count_over_time":
        return jnp.where(has, cnt, nan)
    if func == "avg_over_time":
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        return jnp.where(has, s / jnp.maximum(cnt, 1.0), nan)
    if func == "present_over_time":
        return jnp.where(has, 1.0, nan)
    if func == "absent_over_time":
        return jnp.where(has, nan, 1.0)
    if func in ("stddev_over_time", "stdvar_over_time", "z_score"):
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        s = mmW0(vals) + jnp.where(in_lo, vKlo, 0.0) + jnp.where(in_hi, vKhi, 0.0)
        s2 = (
            mmW0(vals * vals)
            + jnp.where(in_lo, vKlo * vKlo, 0.0)
            + jnp.where(in_hi, vKhi * vKhi, 0.0)
        )
        c = jnp.maximum(cnt, 1.0)
        mean = s / c
        var = jnp.maximum(s2 / c - mean * mean, 0.0)
        if func == "stdvar_over_time":
            return jnp.where(has, var, nan)
        sd = jnp.sqrt(var)
        if func == "stddev_over_time":
            return jnp.where(has, sd, nan)
        ffvL0 = sel(ffv, (_L0,))[:, 0, :]
        v_last = vlast(ffvL0, vKlo, vKhi)
        return jnp.where(has, (v_last - mean) / jnp.maximum(sd, 1e-30), nan)
    if func == "first_over_time":
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        bfvF0 = sel(bfv, (_F0,))[:, 0, :]
        return jnp.where(has, w3(in_lo, vKlo, c0pos, bfvF0, vKhi), nan)
    if func in ("last", "last_over_time"):
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        ffvL0 = sel(ffv, (_L0,))[:, 0, :]
        return jnp.where(has, vlast(ffvL0, vKlo, vKhi), nan)
    if func in ("rate", "increase", "delta"):
        if lean:
            # the backward fill at a VALID klo/khi IS the staged value
            # there (fills copy), so ONE bfv fetch serves all three
            # first/last selection sources; every selected site is valid
            # by its gate, so values stay bit-identical to the classic
            # plan. The window-edge times (bfd@F0, ffd@L0) were already
            # fetched with the membership rows above.
            Bv = sel(bfv, (_F0, _KLO, _KHI))
            bfvF0, vKlo, vKhi = Bv[:, 0, :], Bv[:, 1, :], Bv[:, 2, :]
        else:
            vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
            bfvF0 = sel(bfv, (_F0,))[:, 0, :]
            bfdF0 = sel(bfd, (_F0,))[:, 0, :]
            ffdL0 = sel(ffd, (_L0,))[:, 0, :]
        ffvL0 = sel(ffv, (_L0,))[:, 0, :]
        v_first = w3(in_lo, vKlo, c0pos, bfvF0, vKhi)
        v_last = vlast(ffvL0, vKlo, vKhi)
        tf_rel = w3(in_lo, Klo_rel[None, :] + dKlo, c0pos,
                    F0_rel[None, :] + bfdF0, Khi_rel[None, :] + dKhi)
        tl_rel = w3(in_hi, Khi_rel[None, :] + dKhi, c0pos,
                    L0_rel[None, :] + ffdL0, Klo_rel[None, :] + dKlo)
        dlt = v_last - v_first
        sampled = (tl_rel - tf_rel) * 1e-3
        dur_start = tf_rel * 1e-3
        dur_end = (window_ms.astype(f32) - tl_rel) * 1e-3
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        if is_counter and func != "delta":
            if lean:
                Br = sel(bfraw, (_F0, _KLO, _KHI))
                bfrawF0, rKlo, rKhi = Br[:, 0, :], Br[:, 1, :], Br[:, 2, :]
            else:
                rKlo, rKhi = (a for a in sel(raw, (_KLO, _KHI)).swapaxes(0, 1))
                bfrawF0 = sel(bfraw, (_F0,))[:, 0, :]
            v_first_raw = w3(in_lo, rKlo, c0pos, bfrawF0, rKhi)
            dur_zero = jnp.where(
                dlt > 0, sampled * (v_first_raw / jnp.maximum(dlt, 1e-30)), jnp.inf
            )
            ds = jnp.minimum(dur_start, jnp.where(v_first_raw >= 0, dur_zero, jnp.inf))
        else:
            ds = dur_start
        ds = jnp.where(ds >= thresh, avg_dur / 2.0, ds)
        de = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + ds + de) / jnp.maximum(sampled, 1e-30)
        res = dlt * factor
        if func == "rate":
            res = res / w_s
        return jnp.where(cnt >= 2, res, nan)
    if func in ("irate", "idelta"):
        ok2 = cnt >= 2
        vKlo, vKhi = (a for a in sel(vals, (_KLO, _KHI)).swapaxes(0, 1))
        ffvL0 = sel(ffv, (_L0,))[:, 0, :]
        v_last = vlast(ffvL0, vKlo, vKhi)
        if func == "idelta" and is_counter and not is_delta:
            # diff-encoded counters: the staged value AT the last in-window
            # sample is already the f64-exact last-pair difference
            return jnp.where(ok2, v_last, nan)
        ffdL0 = sel(ffd, (_L0,))[:, 0, :]
        ff2vL0 = sel(ff2v, (_L0,))[:, 0, :]
        ff2dL0 = sel(ff2d, (_L0,))[:, 0, :]
        tl_rel = w3(in_hi, Khi_rel[None, :] + dKhi, c0pos,
                    L0_rel[None, :] + ffdL0, Klo_rel[None, :] + dKlo)
        v_prev = jnp.where(
            in_hi,
            jnp.where(c0pos, ffvL0, vKlo),
            jnp.where(c0ge2, ff2vL0, vKlo),
        )
        tp_rel = jnp.where(
            in_hi,
            jnp.where(c0pos, L0_rel[None, :] + ffdL0, Klo_rel[None, :] + dKlo),
            jnp.where(c0ge2, L0_rel[None, :] + ff2dL0, Klo_rel[None, :] + dKlo),
        )
        dt_s = (tl_rel - tp_rel) * 1e-3
        dv = v_last - v_prev
        r = dv / jnp.maximum(dt_s, 1e-30) if func == "irate" else dv
        return jnp.where(ok2, r, nan)
    raise ValueError(f"masked jitter kernel does not support {func}")


@functools.partial(jax.jit, static_argnames=("is_min", "fetch"))
@jax.named_scope("range_fn")
def jitter_masked_minmax(vals, dev, valid, cc, SEL, idx, tile_mask,
                         edge_onehot, edge_valid, edge_idx, c0pos_g,
                         has_klo, has_khi, blo_rel, ehi_rel,
                         is_min: bool = True, fetch: str = "auto"):
    """Missing-scrape min/max: validity-masked tile hierarchy + edge fetches
    over the certain range, then the <=2 per-series boundary samples. Holes
    carry the sentinel, so validity gating is automatic for value fetches."""
    from .mxu_kernels import use_gather_fetch

    S, T = vals.shape
    Lt = _TILE
    J = tile_mask.shape[0]
    use_gather = use_gather_fetch(fetch, idx)
    v = vals if is_min else -vals
    sentinel = jnp.float32(3e38)
    vm = jnp.where(valid > 0, v, sentinel)
    tmin = vm.reshape(S, T // Lt, Lt).min(-1)
    certain = jnp.where(tile_mask[None, :, :], tmin[:, None, :], sentinel).min(-1)
    if use_gather and edge_idx is not None:
        edges = jnp.take(vm, edge_idx.reshape(-1), axis=1)
    else:
        # matmul fetch reads 0 at holes, not the sentinel: re-mask with a
        # fetched validity so holes can't contaminate the minimum
        edges = jax.lax.dot(vm * jnp.where(valid > 0, 1.0, 0.0), edge_onehot,
                            precision=jax.lax.Precision.HIGHEST)
        eva = jax.lax.dot(valid, edge_onehot,
                          precision=jax.lax.Precision.HIGHEST)
        edges = jnp.where(eva > 0, edges, sentinel)
    edges = edges.reshape(S, J, 2 * Lt)
    edges = jnp.where(edge_valid[None, :, :], edges, sentinel).min(-1)
    r = jnp.minimum(certain, edges)

    def sel_rows(x, lo, hi):
        if use_gather:
            return jnp.take(x, idx[lo:hi].reshape(-1), axis=1).reshape(
                S, hi - lo, J)
        M = SEL.reshape(T, 5, J)[:, lo:hi, :].reshape(T, (hi - lo) * J)
        return jax.lax.dot(
            x, M, precision=jax.lax.Precision.HIGHEST
        ).reshape(S, hi - lo, J)

    def sel_kk(x):
        return sel_rows(x, 3, 5)

    D = sel_kk(dev)
    dKlo, dKhi = D[:, 0, :], D[:, 1, :]
    VA = sel_kk(valid)
    vaKlo, vaKhi = VA[:, 0, :], VA[:, 1, :]
    in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :]) & (vaKlo > 0)
    in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :]) & (vaKhi > 0)
    A = sel_kk(v)
    vKlo, vKhi = A[:, 0, :], A[:, 1, :]
    r = jnp.minimum(r, jnp.where(in_lo, vKlo, sentinel))
    r = jnp.minimum(r, jnp.where(in_hi, vKhi, sentinel))
    # per-series certain count via the validity prefix sum (see
    # jitter_masked_kernel)
    CF = sel_rows(cc, 0, 2)
    vaF0 = sel_rows(valid, 0, 1)[:, 0, :]
    cnt0v = jnp.where(
        c0pos_g[None, :], CF[:, 1, :] - CF[:, 0, :] + vaF0, 0.0
    )
    cnt = cnt0v + in_lo + in_hi
    r = r if is_min else -r
    return jnp.where(cnt > 0, r, jnp.nan)


def masked_window_matrices(block: StagedBlock, start_off: int, step_ms: int,
                           num_steps: int, window_ms: int) -> JitterWindowMatrices:
    g = block.mgrid
    return _cached_window_matrices(
        block, "_mwm_cache", g.nominal_ts, g.n_valid, g.maxdev_ms,
        start_off, step_ms, num_steps, window_ms,
    )


def run_masked_jitter_range_function(func, block: StagedBlock, params,
                                     is_counter=False, is_delta=False,
                                     args=()):
    """Entry: dispatch one missing-scrape range function over block.mgrid.
    Returns a device array [S, J_padded], or None when this (window, grid)
    combination can't use the masked path (caller falls back)."""
    from .kernels import pad_steps
    from .mxu_kernels import fetch_strategy

    g = block.mgrid
    J = pad_steps(params.num_steps)
    start_off = int(params.start_ms - block.base_ms)
    wm = masked_window_matrices(block, start_off, params.step_ms, J,
                                params.window_ms)
    if not wm.ok:
        return None
    fetch = fetch_strategy()
    if func in ("min_over_time", "max_over_time"):
        wm.ensure_minmax()
        return jitter_masked_minmax(
            g.vals, g.dev, g.valid, g.cc, wm.d_SEL, wm.d_idx,
            wm.d_tile_mask, wm.d_edge_onehot, wm.d_edge_valid, wm.d_edge_idx,
            wm.d_c0pos, wm.d_has_klo, wm.d_has_khi, wm.d_blo_rel,
            wm.d_ehi_rel,
            is_min=(func == "min_over_time"), fetch=fetch,
        )
    raw = g.raw if g.raw is not None else g.vals
    bfraw = g.bfraw if g.bfraw is not None else g.bfv
    return jitter_masked_kernel(
        func, g.vals, g.dev, raw, g.valid, g.cc,
        g.ffv, g.ffd, g.bfv, g.bfd, g.ff2v, g.ff2d, bfraw,
        wm.d_W0, wm.d_SEL, wm.d_idx,
        wm.d_c0pos, wm.d_has_klo, wm.d_has_khi,
        wm.d_F0_rel, wm.d_L0_rel, wm.d_Klo_rel, wm.d_Khi_rel,
        wm.d_blo_rel, wm.d_ehi_rel,
        np.float32(params.window_ms),
        is_counter=is_counter, is_delta=is_delta, fetch=fetch,
        maxdev=np.float32(g.maxdev_ms),
    )


def run_jitter_range_function(func, block: StagedBlock, params,
                              is_counter=False, is_delta=False, args=()):
    """Entry: dispatch one jittered-grid range function. Returns a device
    array [S, J_padded], or None when this (window, grid) combination can't
    use the jitter path (caller falls back to the general kernels)."""
    from .kernels import pad_steps

    J = pad_steps(params.num_steps)
    start_off = int(params.start_ms - block.base_ms)
    wm = jitter_window_matrices(block, start_off, params.step_ms, J, params.window_ms)
    if not wm.ok:
        return None
    from .mxu_kernels import fetch_strategy

    dev = block.ts_dev
    fetch = fetch_strategy()
    if func in ("min_over_time", "max_over_time"):
        wm.ensure_minmax()
        return jitter_minmax(
            jnp.asarray(block.vals), dev, wm.d_SEL, wm.d_idx, wm.d_tile_mask,
            wm.d_edge_onehot, wm.d_edge_valid, wm.d_edge_idx, wm.d_count0,
            wm.d_has_klo, wm.d_has_khi, wm.d_blo_rel, wm.d_ehi_rel,
            n_valid=int(np.asarray(block.lens)[0]),
            is_min=(func == "min_over_time"),
            fetch=fetch,
        )
    raw = block.raw if block.raw is not None else block.vals
    return jitter_range_kernel(
        func,
        block.vals,
        dev,
        raw,
        wm.d_W0,
        wm.d_SEL,
        wm.d_idx,
        wm.d_count0, wm.d_c0pos, wm.d_c0ge2, wm.d_has_klo, wm.d_has_khi,
        wm.d_F0_rel, wm.d_L0_rel, wm.d_L2_rel, wm.d_Klo_rel, wm.d_Khi_rel,
        wm.d_blo_rel, wm.d_ehi_rel,
        np.float32(params.window_ms),
        is_counter=is_counter,
        is_delta=is_delta,
        fetch=fetch,
    )


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.mxu_jitter",
        jitter_range_kernel=jitter_range_kernel,
        jitter_minmax=jitter_minmax,
        jitter_masked_kernel=jitter_masked_kernel,
        jitter_masked_minmax=jitter_masked_minmax,
    )


_register_kernel_observatory()
