"""Time-axis sharding with ring halo exchange — the ring-attention analog
for range queries (SURVEY.md §5 "long-context": sharded time blocks with a
±lookback halo exchange; reference analog: time-splitting planners +
lookback-window sharing).

For very long ranges the time dimension, not series count, dominates. The
staged block's time axis shards across the mesh into DISJOINT sample
slices; device d computes the output steps inside its span. Windows at a
slice's left edge reach up to ``window`` back into the previous slice, so
at runtime each device sends the right-aligned TAIL of its slice to its
right neighbor with ONE ``ppermute`` over ICI — exactly ring attention's
KV halo pattern with the lookback window as the attention span.

Sort discipline that makes the general kernel work unchanged on the
concatenated [halo | slice] array: halo padding uses an INT32_MIN sentinel
(sorts before every real sample and never lands in a window because window
lower bounds are real times), so boundary counting, prefix sums, and
positional gathers stay exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import kernels as K
from ..ops.staging import TS_PAD, StagedBlock

TS_NEG = np.int32(-(2**31) + 1)  # sorts before all real samples


def make_time_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), axis_names=("time",))


def split_time_axis(block: StagedBlock, n_devices: int, window_ms: int,
                    start_ms: int, step_ms: int, num_steps: int):
    """Host-side prep: disjoint per-device sample slices + right-aligned
    tails for the halo exchange.

    Device d owns steps [d*J_dev, (d+1)*J_dev) and the samples in
    (owned_end[d-1], owned_end[d]] (device 0 additionally owns the global
    lookback span before the first step). Halo width H = max samples any
    window needs from the previous slice, measured from the data.

    Returns (ts [D,S,Tl], vals, raw, lens [D,S], tail_ts [D,S,H],
    tail_vals, tail_raw, j_dev).
    """
    D = n_devices
    S, T = block.ts.shape
    ts = np.asarray(block.ts)
    vals = np.asarray(block.vals)
    raw = np.asarray(block.raw) if block.raw is not None else vals
    lens = np.asarray(block.lens)
    J_dev = -(-num_steps // D)
    start_off = start_ms - block.base_ms
    owned_end = [start_off + (min((d + 1) * J_dev, num_steps) - 1) * step_ms for d in range(D)]
    owned_start = [start_off - window_ms] + owned_end[:-1]
    bounds = np.empty((D, S, 2), dtype=np.int64)
    Tl = 1
    H = 1
    for d in range(D):
        for s in range(S):
            row = ts[s, : lens[s]]
            lo = np.searchsorted(row, owned_start[d], side="right")
            hi = np.searchsorted(row, owned_end[d], side="right")
            bounds[d, s] = (lo, hi)
            Tl = max(Tl, hi - lo)
            if d > 0:
                # halo this device needs: samples in the previous slice
                # within window of its first step
                first_step = start_off + d * J_dev * step_ms
                need_lo = np.searchsorted(row, first_step - window_ms, side="right")
                H = max(H, lo - min(need_lo, lo))
    Tl = max(((int(Tl) + 127) // 128) * 128, 128)
    H = max(((int(H) + 127) // 128) * 128, 128)
    out_ts = np.full((D, S, Tl), TS_PAD, dtype=np.int32)
    out_vals = np.zeros((D, S, Tl), dtype=np.float32)
    out_raw = np.zeros((D, S, Tl), dtype=np.float32)
    out_lens = np.zeros((D, S), dtype=np.int32)
    tail_ts = np.full((D, S, H), TS_NEG, dtype=np.int32)
    tail_vals = np.zeros((D, S, H), dtype=np.float32)
    tail_raw = np.zeros((D, S, H), dtype=np.float32)
    for d in range(D):
        for s in range(S):
            lo, hi = bounds[d, s]
            n = hi - lo
            out_ts[d, s, :n] = ts[s, lo:hi]
            out_vals[d, s, :n] = vals[s, lo:hi]
            out_raw[d, s, :n] = raw[s, lo:hi]
            out_lens[d, s] = n
            # right-aligned tail of THIS device's slice (sent to d+1)
            k = min(H, n)
            if k:
                tail_ts[d, s, H - k :] = ts[s, hi - k : hi]
                tail_vals[d, s, H - k :] = vals[s, hi - k : hi]
                tail_raw[d, s, H - k :] = raw[s, hi - k : hi]
    return out_ts, out_vals, out_raw, out_lens, tail_ts, tail_vals, tail_raw, J_dev


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "func", "j_dev", "is_counter", "is_delta"),
)
def timesharded_range(
    mesh: Mesh,
    func: str,
    ts, vals, raw,  # [D, S, Tl] disjoint slices
    lens,  # [D, S]
    tail_ts, tail_vals, tail_raw,  # [D, S, H] right-aligned own tails
    baseline,  # [S] replicated
    start_off, step_ms, window,
    j_dev: int,
    is_counter: bool = False,
    is_delta: bool = False,
):
    """One compiled program: ppermute halo to the right neighbor, then the
    standard range kernel per device on [halo | slice]. Returns
    [D, S, j_dev] step grids (device-major)."""
    D = mesh.devices.size
    axis = mesh.axis_names[0]  # works over any single-axis mesh name
    perm = [(i, (i + 1) % D) for i in range(D)]

    def local(ts_l, vals_l, raw_l, lens_l, tts, tv, tr, base):
        d = jax.lax.axis_index(axis)
        # halo arrives from the LEFT neighbor (ring shift right)
        h_ts = jax.lax.ppermute(tts, axis, perm)[0]
        h_v = jax.lax.ppermute(tv, axis, perm)[0]
        h_r = jax.lax.ppermute(tr, axis, perm)[0]
        # device 0 has no left neighbor: neutralize the wrapped halo
        h_ts = jnp.where(d == 0, jnp.int32(TS_NEG), h_ts)
        h_v = jnp.where(d == 0, 0.0, h_v)
        h_r = jnp.where(d == 0, 0.0, h_r)
        H = h_ts.shape[1]
        comb_ts = jnp.concatenate([h_ts, ts_l[0]], axis=1)
        comb_v = jnp.concatenate([h_v, vals_l[0]], axis=1)
        comb_r = jnp.concatenate([h_r, raw_l[0]], axis=1)
        comb_lens = lens_l[0] + H  # sentinel slots sort first and never match
        my_start = start_off + d.astype(jnp.int32) * j_dev * step_ms
        grid = K.range_kernel(
            func, comb_ts, comb_v, comb_lens, base, comb_r,
            my_start, step_ms, window, j_dev,
            is_counter=is_counter, is_delta=is_delta,
        )
        return grid[None]

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), P()),
        out_specs=P(axis, None, None),
        check_vma=False,
    )(ts, vals, raw, lens, tail_ts, tail_vals, tail_raw, baseline)


def run_timesharded(mesh: Mesh, func: str, block: StagedBlock, params: K.RangeParams,
                    is_counter=False, is_delta=False):
    """Host entry: shard the time axis over the mesh and execute. Returns
    [S, num_steps] (numpy-sliceable device array)."""
    D = mesh.devices.size
    ts, vals, raw, lens, tts, tv, tr, j_dev = split_time_axis(
        block, D, params.window_ms, params.start_ms, params.step_ms, params.num_steps
    )
    dev = NamedSharding(mesh, P(mesh.axis_names[0]))
    rep = NamedSharding(mesh, P())
    out = timesharded_range(
        mesh, func,
        jax.device_put(ts, dev), jax.device_put(vals, dev), jax.device_put(raw, dev),
        jax.device_put(lens, dev),
        jax.device_put(tts, dev), jax.device_put(tv, dev), jax.device_put(tr, dev),
        jax.device_put(np.asarray(block.baseline), rep),
        np.int32(params.start_ms - block.base_ms),
        np.int32(params.step_ms), np.int32(params.window_ms),
        j_dev, is_counter=is_counter, is_delta=is_delta,
    )
    S = out.shape[1]
    flat = jnp.moveaxis(out, 0, 1).reshape(S, -1)
    return flat[:, : params.num_steps]
