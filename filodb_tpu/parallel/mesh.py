"""Device-mesh distributed query execution (reference's scatter-gather over
Akka/Arrow-Flight — SURVEY.md §2 "Distributed communication backends" — is
replaced by XLA collectives over ICI: shards live on devices of one mesh, and
ReduceAggregateExec's cross-node merge becomes a psum).

Layout: the mesh has one axis, ``shard``. A query's staged blocks are
concatenated over series with equal per-device padding, sharded
``P('shard', None)``. One jit computes: range function on the local block,
local segment-reduce into label groups, then ``psum`` over the shard axis —
the whole distributed ``sum by (rate(...))`` in one compiled program with no
host round-trips.

Multi-host: the same program runs under ``jax.distributed`` with DCN-backed
meshes — the planner hierarchy stays identical (reference's
MultiPartitionPlanner analog would split across meshes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import aggregations as AGG
from ..ops import kernels as K
from ..ops.staging import StagedBlock, pad_series


def make_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), axis_names=("shard",))


def make_series_mesh(devices=None) -> Mesh:
    """1-D mesh for the series-sharded fused superblock path
    (PartitionSpec('series', None) placement in ops/staging)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), axis_names=("series",))


def series_mesh(mesh) -> Mesh:
    """Normalize any configured mesh to the 1-D form the sharded fused
    kernels partition the superblock series axis over: 1-D meshes pass
    through (whatever the axis is named), multi-axis meshes (shard x time)
    flatten their devices onto a fresh ``series`` axis. Mesh equality is by
    (devices, axis names), so repeated normalizations hit the same jit
    cache entries."""
    if len(mesh.axis_names) == 1:
        return mesh
    return make_series_mesh(list(mesh.devices.flat))


def _segment_psum(op: str, grid, gids_l, num_groups: int):
    """Local segment-reduce + psum over the shard axis (shared by the
    general and MXU local kernels). The ONE definition lives in
    ops/aggregations._segment_psum_axis, shared with the sharded fused
    superblock path."""
    return AGG._segment_psum_axis(op, grid, gids_l, num_groups, "shard")


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "func", "op", "num_groups", "is_counter", "is_delta"),
)
def distributed_agg_range_mxu(
    mesh: Mesh,
    func: str,
    op: str,
    vals, raw,  # [D*S, T] sharded
    lens, baseline, gids,  # [D*S]
    W, F, L, L2,  # [T, J] replicated window matrices
    count, t_first, t_last, t_last2, out_t,  # [J] replicated
    window_ms,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
):
    """Regular-grid mesh aggregation: the MXU matmul kernel inside shard_map
    (one compiled program; on one device this collapses a multi-shard query
    to a single kernel invocation)."""
    from ..ops.mxu_kernels import mxu_range_kernel

    def local(vals_l, raw_l, lens_l, base_l, gids_l):
        grid = mxu_range_kernel(
            func, vals_l, raw_l, base_l, W, F, L, L2,
            count, t_first, t_last, t_last2, out_t, window_ms,
            is_counter=is_counter, is_delta=is_delta,
        )
        # padded rows (lens 0) would read as zero-valued series: mask them
        grid = jnp.where((lens_l > 0)[:, None], grid, jnp.nan)
        return _segment_psum(op, grid, gids_l, num_groups)

    shard = P("shard")
    row = P("shard", None)
    rep = P()
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row, row, shard, shard, shard),
        out_specs=rep,
        check_vma=False,
    )(vals, raw, lens, baseline, gids)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "func", "op", "num_groups", "is_counter", "is_delta", "fetch"
    ),
)
def distributed_agg_range_jitter(
    mesh: Mesh,
    func: str,
    op: str,
    vals, raw, dev,  # [D*S, T] sharded
    lens, gids,  # [D*S]
    W0,  # [T, J] replicated certain-membership matrix (mxu_jitter)
    SEL,  # [T, 5J] replicated boundary one-hot stack
    idx,  # [5, J] i32 replicated gather form (or None)
    count0, c0pos, c0ge2, has_klo, has_khi,  # [J] replicated
    F0_rel, L0_rel, L2_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,  # [J]
    window_ms,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
    fetch: str = "auto",
):
    """Near-regular (jittered) grid mesh aggregation: the certain-membership
    matmul + per-series boundary-correction kernel (ops/mxu_jitter.py) inside
    shard_map, so jittered real-world scrape data keeps the single-program
    multi-shard MXU path."""
    from ..ops.mxu_jitter import jitter_range_kernel

    def local(vals_l, raw_l, dev_l, lens_l, gids_l):
        grid = jitter_range_kernel(
            func, vals_l, dev_l, raw_l, W0, SEL, idx,
            count0, c0pos, c0ge2, has_klo, has_khi,
            F0_rel, L0_rel, L2_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,
            window_ms, is_counter=is_counter, is_delta=is_delta, fetch=fetch,
        )
        grid = jnp.where((lens_l > 0)[:, None], grid, jnp.nan)
        return _segment_psum(op, grid, gids_l, num_groups)

    shard = P("shard")
    row = P("shard", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row, row, row, shard, shard),
        out_specs=P(),
        check_vma=False,
    )(vals, raw, dev, lens, gids)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "func", "op", "num_groups", "is_counter", "is_delta", "fetch"
    ),
)
def distributed_agg_range_masked(
    mesh: Mesh,
    func: str,
    op: str,
    vals, dev, raw, valid, cc,  # [D*S, T] sharded slot-aligned masked arrays
    ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw,  # [D*S, T] sharded fills
    lens, gids,  # [D*S]
    W0, SEL, idx,  # replicated window structure (mxu_jitter)
    c0pos_g, has_klo, has_khi,  # [J] replicated
    F0_rel, L0_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,  # [J]
    window_ms,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
    fetch: str = "auto",
):
    """Missing-scrape mesh aggregation: the masked jitter kernel
    (ops/mxu_jitter.jitter_masked_kernel) inside shard_map, so a dropped
    scrape keeps multi-shard queries on the single-program MXU path."""
    from ..ops.mxu_jitter import jitter_masked_kernel

    def local(vals_l, dev_l, raw_l, valid_l, cc_l, ffv_l, ffd_l, bfv_l,
              bfd_l, ff2v_l, ff2d_l, bfraw_l, lens_l, gids_l):
        grid = jitter_masked_kernel(
            func, vals_l, dev_l, raw_l, valid_l, cc_l,
            ffv_l, ffd_l, bfv_l, bfd_l, ff2v_l, ff2d_l, bfraw_l,
            W0, SEL, idx, c0pos_g, has_klo, has_khi,
            F0_rel, L0_rel, Klo_rel, Khi_rel, blo_rel, ehi_rel,
            window_ms, is_counter=is_counter, is_delta=is_delta, fetch=fetch,
        )
        grid = jnp.where((lens_l > 0)[:, None], grid, jnp.nan)
        return _segment_psum(op, grid, gids_l, num_groups)

    shard = P("shard")
    row = P("shard", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row,) * 12 + (shard, shard),
        out_specs=P(),
        check_vma=False,
    )(vals, dev, raw, valid, cc, ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw,
      lens, gids)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "func", "op", "num_steps", "num_groups", "is_counter", "is_delta"),
)
def distributed_agg_range(
    mesh: Mesh,
    func: str,
    op: str,
    ts,  # [D*S, T] i32, sharded over devices
    vals,  # [D*S, T] f32
    lens,  # [D*S] i32
    baseline,  # [D*S] f32
    raw,  # [D*S, T] f32
    gids,  # [D*S] i32 group ids (global group numbering)
    start_off,
    step_ms,
    window,
    num_steps: int,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
):
    """sum/min/max/count/avg-by over a range function, sharded over the mesh.

    Returns [num_groups, num_steps] — already reduced across every shard via
    psum on ICI (the on-device form of ReduceAggregateExec).
    """

    def local(ts_l, vals_l, lens_l, base_l, raw_l, gids_l):
        grid = K.range_kernel(
            func, ts_l, vals_l, lens_l, base_l, raw_l,
            start_off, step_ms, window, num_steps,
            is_counter=is_counter, is_delta=is_delta,
        )
        return _segment_psum(op, grid, gids_l, num_groups)

    shard = P("shard")
    row = P("shard", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row, row, shard, shard, row, shard),
        out_specs=P(),
        check_vma=False,
    )(ts, vals, lens, baseline, raw, gids)


def _mesh_layout(blocks: list[StagedBlock], n_devices: int):
    """Shared row layout for every mesh stacker: round-robin blocks over
    devices, one padded row band per device."""
    D = n_devices
    T = max(b.ts.shape[1] for b in blocks)
    per_dev: list[list[int]] = [[] for _ in range(D)]
    for i in range(len(blocks)):
        per_dev[i % D].append(i)
    S_dev = pad_series(max(1, max(
        sum(blocks[i].n_series for i in idxs) for idxs in per_dev
    )))
    return per_dev, S_dev, T


def stack_masked_for_mesh(blocks: list[StagedBlock], n_devices: int):
    """Stack the MaskedGrid sidecars (missing-scrape mesh path) using the
    SAME row layout as stack_blocks_for_mesh, recomputing the fills over the
    stacked width so padding columns carry correct forward/backward fills.
    Caller guarantees every non-empty block has a harmonized mgrid.
    Returns (vals, dev, raw, valid, cc, ffv, ffd, bfv, bfd, ff2v, ff2d,
    bfraw), all [D*S, T] f32."""
    from ..ops.staging import masked_fills

    per_dev, S_dev, _ = _mesh_layout(blocks, n_devices)
    # masked sidecars size by SLOT span, which can exceed the packed T
    T = max(b.mgrid.valid.shape[1] for b in blocks if b.mgrid is not None)
    D = n_devices
    N = D * S_dev
    vals = np.zeros((N, T), dtype=np.float32)
    dev = np.zeros((N, T), dtype=np.float32)
    raw = np.zeros((N, T), dtype=np.float32)
    valid = np.zeros((N, T), dtype=np.float32)
    g0 = next(b.mgrid for b in blocks if b.n_series > 0)
    interval = g0.interval_ms
    R0 = int(np.asarray(g0.nominal_ts)[0])
    R = np.rint(R0 + np.arange(T, dtype=np.float64) * interval).astype(np.int64)
    for d, idxs in enumerate(per_dev):
        o = d * S_dev
        for i in idxs:
            b = blocks[i]
            k = b.n_series
            if k == 0:
                continue
            g = b.mgrid
            t = g.valid.shape[1]
            valid[o : o + k, :t] = np.asarray(g.valid)[:k]
            vals[o : o + k, :t] = np.asarray(g.vals)[:k]
            dev[o : o + k, :t] = np.asarray(g.dev)[:k]
            raw_src = g.raw if g.raw is not None else g.vals
            raw[o : o + k, :t] = np.asarray(raw_src)[:k]
            o += k
    ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw = masked_fills(
        valid, vals, dev, raw, R
    )
    cc = np.cumsum(valid, axis=1, dtype=np.float64).astype(np.float32)
    return vals, dev, raw, valid, cc, ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw


def stack_blocks_for_mesh(blocks: list[StagedBlock], gids_per_block: list[np.ndarray], n_devices: int,
                          with_dev: bool = False):
    """Concatenate per-shard staged blocks into mesh-shardable arrays.

    Blocks distribute round-robin over devices (several shards may share a
    device — the single-chip case packs ALL shards into one block). Padded
    rows get group id 0 with len 0 (they contribute nothing).
    With ``with_dev``, also returns the stacked [D*S, T] timestamp-deviation
    matrix for the jittered-grid mesh path (zeros where a block has none)."""
    D = n_devices
    per_dev, S_dev, T = _mesh_layout(blocks, n_devices)
    ts = np.full((D * S_dev, T), np.int32(2**31 - 1), dtype=np.int32)
    vals = np.zeros((D * S_dev, T), dtype=np.float32)
    raw = np.zeros((D * S_dev, T), dtype=np.float32)
    lens = np.zeros(D * S_dev, dtype=np.int32)
    baseline = np.zeros(D * S_dev, dtype=np.float32)
    gids = np.zeros(D * S_dev, dtype=np.int32)
    dev = np.zeros((D * S_dev, T), dtype=np.float32) if with_dev else None
    for d, idxs in enumerate(per_dev):
        o = d * S_dev
        for i in idxs:
            b, g = blocks[i], gids_per_block[i]
            t = b.ts.shape[1]
            k = b.n_series
            ts[o : o + k, :t] = np.asarray(b.ts)[:k]
            vals[o : o + k, :t] = np.asarray(b.vals)[:k]
            raw_src = b.raw if b.raw is not None else b.vals
            raw[o : o + k, :t] = np.asarray(raw_src)[:k]
            lens[o : o + k] = np.asarray(b.lens)[:k]
            baseline[o : o + k] = np.asarray(b.baseline)[:k]
            gids[o : o + k] = g
            if with_dev and b.ts_dev is not None:
                dev[o : o + k, :t] = np.asarray(b.ts_dev)[:k]
            o += k
    if with_dev:
        return ts, vals, lens, baseline, raw, gids, dev
    return ts, vals, lens, baseline, raw, gids


def shard_arrays(mesh: Mesh, ts, vals, lens, baseline, raw, gids):
    """Place the stacked arrays on the mesh with shard-axis sharding."""
    row = NamedSharding(mesh, P("shard", None))
    vec = NamedSharding(mesh, P("shard"))
    return (
        jax.device_put(ts, row),
        jax.device_put(vals, row),
        jax.device_put(lens, vec),
        jax.device_put(baseline, vec),
        jax.device_put(raw, row),
        jax.device_put(gids, vec),
    )
