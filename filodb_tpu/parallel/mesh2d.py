"""2D mesh execution: series-parallel x time-parallel in one program.

The full SPMD composition for ``sum by (...) (rate(m[w]))`` over both huge
cardinality AND long ranges: mesh axes ``(shard, time)`` —

- the ``shard`` axis partitions series (data-parallel); cross-series
  aggregation is a ``psum`` over it (parallel/mesh.py's pattern);
- the ``time`` axis partitions samples (the sequence-parallel axis); window
  lookback crosses slice boundaries via a ring ``ppermute`` halo
  (parallel/timeshard.py's pattern).

One jit: per-tile range kernel -> local segment-reduce -> psum(shard);
outputs concatenate along the step axis across the time ring. This is the
TSDB analog of dp+sp sharding in model training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import kernels as K
from ..ops.staging import StagedBlock
from .timeshard import TS_NEG, split_time_axis


def make_mesh2d(n_shard: int, n_time: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    assert len(devices) >= n_shard * n_time
    arr = np.array(devices[: n_shard * n_time]).reshape(n_shard, n_time)
    return Mesh(arr, axis_names=("shard", "time"))


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "func", "op", "j_dev", "num_groups", "is_counter", "is_delta"),
)
def mesh2d_agg_range(
    mesh: Mesh,
    func: str,
    op: str,
    ts, vals, raw,  # [Ds*S_l, Dt, Tl] — series blocks x time slices
    lens,  # [Ds*S_l, Dt]
    tail_ts, tail_vals, tail_raw,  # [Ds*S_l, Dt, H]
    gids,  # [Ds*S_l] global group ids
    baseline,  # [Ds*S_l]
    start_off, step_ms, window,
    j_dev: int,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
):
    Dt = mesh.shape["time"]
    perm = [(i, (i + 1) % Dt) for i in range(Dt)]

    def local(ts_l, vals_l, raw_l, lens_l, tts, tv, tr, gids_l, base_l):
        # [S_l, 1, Tl] tiles: drop the time-slice axis
        t_idx = jax.lax.axis_index("time")
        h_ts = jax.lax.ppermute(tts, "time", perm)[:, 0]
        h_v = jax.lax.ppermute(tv, "time", perm)[:, 0]
        h_r = jax.lax.ppermute(tr, "time", perm)[:, 0]
        h_ts = jnp.where(t_idx == 0, jnp.int32(TS_NEG), h_ts)
        h_v = jnp.where(t_idx == 0, 0.0, h_v)
        h_r = jnp.where(t_idx == 0, 0.0, h_r)
        H = h_ts.shape[1]
        comb_ts = jnp.concatenate([h_ts, ts_l[:, 0]], axis=1)
        comb_v = jnp.concatenate([h_v, vals_l[:, 0]], axis=1)
        comb_r = jnp.concatenate([h_r, raw_l[:, 0]], axis=1)
        comb_lens = lens_l[:, 0] + H
        my_start = start_off + t_idx.astype(jnp.int32) * j_dev * step_ms
        grid = K.range_kernel(
            func, comb_ts, comb_v, comb_lens, base_l, comb_r,
            my_start, step_ms, window, j_dev,
            is_counter=is_counter, is_delta=is_delta,
        )
        valid = ~jnp.isnan(grid)
        v0 = jnp.where(valid, grid, 0.0)
        s = jax.ops.segment_sum(v0, gids_l, num_groups)
        c = jax.ops.segment_sum(valid.astype(jnp.float32), gids_l, num_groups)
        s = jax.lax.psum(s, "shard")
        c = jax.lax.psum(c, "shard")
        if op == "sum":
            out = jnp.where(c > 0, s, jnp.nan)
        elif op == "count":
            out = jnp.where(c > 0, c, jnp.nan)
        elif op == "avg":
            out = jnp.where(c > 0, s / jnp.maximum(c, 1.0), jnp.nan)
        else:
            raise ValueError(f"2d mesh aggregation supports sum/count/avg, got {op}")
        return out[None, None]  # [1, 1, G, j_dev]

    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("shard", "time"), P("shard", "time"), P("shard", "time"),
            P("shard", "time"),
            P("shard", "time"), P("shard", "time"), P("shard", "time"),
            P("shard"), P("shard"),
        ),
        out_specs=P("shard", "time", None, None),
        check_vma=False,
    )(ts, vals, raw, lens, tail_ts, tail_vals, tail_raw, gids, baseline)
    # [Ds, Dt, G, j_dev]: shard axis already reduced (psum) — take slice 0,
    # concat time along steps
    out = out[0]  # [Dt, G, j_dev]
    return jnp.moveaxis(out, 0, 1).reshape(out.shape[1], -1)  # [G, Dt*j_dev]


def run_mesh2d(mesh: Mesh, func: str, op: str, blocks: list[StagedBlock],
               gids_per_block, num_groups: int, params: K.RangeParams,
               is_counter=False, is_delta=False):
    """blocks: one staged block per series shard (<= mesh 'shard' size).
    Each block's time axis is split across the 'time' axis with halos."""
    Ds = mesh.shape["shard"]
    Dt = mesh.shape["time"]
    assert len(blocks) <= Ds
    # per-shard time split, then stack along a padded series axis
    parts = [
        split_time_axis(b, Dt, params.window_ms, params.start_ms, params.step_ms, params.num_steps)
        for b in blocks
    ]
    j_dev = parts[0][-1]
    S_l = max(p[0].shape[1] for p in parts)
    Tl = max(p[0].shape[2] for p in parts)
    H = max(p[4].shape[2] for p in parts)

    def stack(idx, fill, dtype, width):
        out = np.full((Ds * S_l, Dt, width), fill, dtype=dtype)
        for bi, p in enumerate(parts):
            arr = p[idx]  # [Dt, S_b, w]
            out[bi * S_l : bi * S_l + arr.shape[1], :, : arr.shape[2]] = np.moveaxis(arr, 0, 1)
        return out

    from ..ops.staging import TS_PAD

    ts = stack(0, TS_PAD, np.int32, Tl)
    vals = stack(1, 0.0, np.float32, Tl)
    raw = stack(2, 0.0, np.float32, Tl)
    tail_ts = stack(4, TS_NEG, np.int32, H)
    tail_vals = stack(5, 0.0, np.float32, H)
    tail_raw = stack(6, 0.0, np.float32, H)
    lens = np.zeros((Ds * S_l, Dt), dtype=np.int32)
    gids = np.zeros(Ds * S_l, dtype=np.int32)
    baseline = np.zeros(Ds * S_l, dtype=np.float32)
    for bi, (p, b, g) in enumerate(zip(parts, blocks, gids_per_block)):
        lens[bi * S_l : bi * S_l + p[3].shape[1], :] = np.moveaxis(p[3], 0, 1)
        k = b.n_series
        gids[bi * S_l : bi * S_l + k] = g
        baseline[bi * S_l : bi * S_l + k] = np.asarray(b.baseline)[:k]
        # padded series rows: zero-length, group 0 — contribute nothing
    sh2 = NamedSharding(mesh, P("shard", "time"))
    sh1 = NamedSharding(mesh, P("shard"))
    out = mesh2d_agg_range(
        mesh, func, op,
        jax.device_put(ts, sh2), jax.device_put(vals, sh2), jax.device_put(raw, sh2),
        jax.device_put(lens, sh2),
        jax.device_put(tail_ts, sh2), jax.device_put(tail_vals, sh2),
        jax.device_put(tail_raw, sh2),
        jax.device_put(gids, sh1), jax.device_put(baseline, sh1),
        np.int32(params.start_ms - blocks[0].base_ms),
        np.int32(params.step_ms), np.int32(params.window_ms),
        j_dev, num_groups,
        is_counter=is_counter, is_delta=is_delta,
    )
    return out[:, : params.num_steps]