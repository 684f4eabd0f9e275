"""The fused program family (ops/aggregations: FUSED_BODIES x placement x
lanes behind ``_fused_program_jit``).

A fused program is composed from three parts that are each written once: a
range body, a placement (one device, or one shard_map frame over a series
mesh) and a lane plan (one query, or the unrolled lanes of a cross-query
batch). What the table marks supported must therefore be bit-equal to the
same body on one device for one query; what it marks unsupported must be
refused before grouping (``batch_variant_supported``), at dispatch (the
backstop) and at trace time (the builder); and every body the selection can
name must exist.

Bit-equality across a mesh needs an order-free combine: the scalar family
reduces with ``max`` (pmax), the hist family sums integer bucket counts
(``last``: exact in f32 whatever the order). Lanes are bit-equal whatever the
epilogue, because a lane's subgraph IS the single-query computation.

Runs on the conftest-forced 8-device virtual CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import staging as ST
from filodb_tpu.ops.kernels import RangeParams, pad_steps
from filodb_tpu.parallel.mesh import make_mesh

pytestmark = [pytest.mark.perf]

BASE = 1_600_000_000_000
INTERVAL = 10_000
N, T, B = 20, 96, 4
P1 = RangeParams(BASE + 400_000, 60_000, 6, 300_000)
P2 = RangeParams(BASE + 460_000, 60_000, 6, 120_000)
J_PAD = pad_steps(P1.num_steps)
LES = np.array([0.1, 1.0, 10.0, np.inf], np.float32)

# body -> (grid class its selection needs, a function that selects it)
SELECTS = {
    "general": ("regular", "deriv"),
    "mxu": ("regular", "rate"),
    "jitter": ("jitter", "rate"),
    "masked": ("holes", "rate"),
    "jitter_minmax": ("jitter", "max_over_time"),
    "masked_minmax": ("holes", "min_over_time"),
    "pallas": ("irregular", "rate"),
    "hist_general": ("hist_irregular", "last"),
    "hist_shared": ("hist_regular", "last"),
    "hist_jitter": ("hist_jitter", "last"),
}
# the epilogues each body is composed with (the first is order-free across
# a mesh; mxu and hist_shared also carry the ones with other output trees)
EPILOGUES = {
    name: [("agg", "max")] for name in SELECTS if not name.startswith("hist")
}
EPILOGUES["mxu"] += [("topk", 2, False), ("quantile",)]
EPILOGUES.update({name: [("hist", "sum")] for name in SELECTS
                  if name.startswith("hist")})
EPILOGUES["hist_shared"] += [("hist", "quantile")]
CASES = [(b, e) for b in SELECTS for e in EPILOGUES[b]]
FORMS = ["mesh", "lanes", "mesh+lanes"]


def _times(kind, rng):
    nominal = BASE + INTERVAL // 2 + (1 + np.arange(T, dtype=np.int64)) * INTERVAL
    if kind == "regular":
        return nominal, np.ones(T, bool)
    if kind == "irregular":
        return np.sort(BASE + rng.choice(
            np.arange(1_000, T * INTERVAL, 7), T, replace=False)
        ).astype(np.int64), np.ones(T, bool)
    ts = nominal + np.rint(rng.uniform(-0.05, 0.05, T) * INTERVAL).astype(np.int64)
    keep = np.ones(T, bool)
    if kind == "holes":
        keep[rng.choice(np.arange(1, T - 1), 2, replace=False)] = False
    return ts, keep


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.fixture(scope="module")
def blocks(mesh):
    """grid class -> (superblock on one device, the same rows on the mesh)."""
    out = {}
    for kind in ("regular", "jitter", "holes", "irregular"):
        rng = np.random.default_rng(len(kind))
        series = []
        for _ in range(N):
            ts, keep = _times(kind, rng)
            v = np.cumsum(rng.uniform(0, 10, T)) + 1e6
            series.append((ts[keep], v[keep]))
        staged = ST.stage_series(series, BASE, [(0, i) for i in range(N)],
                                 counter_corrected=True)
        assert ST.grid_class(staged) == kind
        out[kind] = staged
        rng = np.random.default_rng(len(kind))
        hseries = []
        for _ in range(N):
            ts, _keep = _times("jitter" if kind == "holes" else kind, rng)
            incr = rng.poisson(2.0, size=(T, B)).astype(np.float64)
            hseries.append((ts, np.cumsum(np.cumsum(incr, axis=1), axis=0)))
        out["hist_" + kind] = ST.stage_histogram_series(hseries, BASE, B)
    return {
        k: (ST.build_superblock([b])[0], ST.build_superblock([b], mesh=mesh)[0])
        for k, b in out.items() if k != "hist_holes"
    }


@pytest.fixture(autouse=True)
def _pallas_on(monkeypatch):
    # the irregular class promotes to the Pallas body (interpreted here)
    monkeypatch.setenv("FILODB_PALLAS", "1")


def _gids(block, groups, mesh=None):
    s_pad = np.asarray(block.lens).shape[0]
    g = np.full(s_pad, groups, np.int32)
    g[: block.n_series] = np.arange(block.n_series) % groups
    return ST.series_put(mesh)(g)


def _single(body, epilogue, block, gids, groups, qv, params, mesh=None):
    func = SELECTS[body][1]
    if epilogue[0] == "hist":
        return AGG.fused_hist_range_aggregate(
            func, block, gids, groups, params, jnp.asarray(LES),
            q=float(qv) if epilogue[1] == "quantile" else None, mesh=mesh)
    if epilogue[0] == "topk":
        return AGG.fused_topk(func, block, epilogue[1], epilogue[2], params,
                              is_counter=True, mesh=mesh)
    if epilogue[0] == "quantile":
        return AGG.fused_quantile(func, block, gids, groups, float(qv),
                                  params, is_counter=True, mesh=mesh)
    return AGG.fused_range_aggregate(func, epilogue[1], block, gids, groups,
                                     params, is_counter=True, mesh=mesh)


def _batched(body, epilogue, block, lanes, groups, mesh=None):
    func = SELECTS[body][1]
    if epilogue[0] == "hist":
        return AGG.fused_batched_hist(
            func, block, lanes, groups, J_PAD, jnp.asarray(LES),
            epilogue[1] == "quantile", False, mesh=mesh)
    return AGG.fused_batched_scalar(func, epilogue, block, lanes, groups,
                                    J_PAD, True, False, mesh=mesh)


def _lanes(block, epilogue, mesh=None):
    """Three lanes over two windows and two group-bys (padded to four)."""
    if epilogue[0] == "topk":
        g = AGG.zero_gids(block)
        return [(g, 0.0, P1, 1), (g, 0.0, P2, 1)]
    ga, gb = _gids(block, 2, mesh), _gids(block, 4, mesh)
    return [(ga, 0.5, P1, 2), (gb, 0.9, P2, 4), (gb, 0.9, P1, 4)]


def _equal(got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(b).any()
        assert a.tobytes() == b.tobytes()


def _supported(body, form):
    b = AGG.FUSED_BODIES[body]
    return ((b.mesh or "mesh" not in form) and (b.lanes or "lanes" not in form))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("body,epilogue", CASES,
                         ids=[f"{b}-{'_'.join(map(str, e))}" for b, e in CASES])
def test_a_composed_program_is_its_body_on_one_device_for_one_query(
        body, epilogue, form, blocks, mesh):
    kind, func = SELECTS[body]
    one, sharded = blocks[kind]
    hist = epilogue[0] == "hist"
    assert AGG._fused_body(hist, one, func, False, None)[0] == body
    m = mesh if "mesh" in form else None
    block = sharded if m is not None else one
    if not _supported(body, form):
        if AGG._fused_body(hist, block, func, False, m)[0] == body:
            # selected, so it is the lanes it lacks: refused before
            # grouping, and again at dispatch
            assert not AGG.batch_variant_supported(
                block, func, "hist" if hist else epilogue[0], False, m)
            with pytest.raises(RuntimeError, match="per-lane dispatch"):
                _batched(body, epilogue, block,
                         [l[:3] for l in _lanes(block, epilogue, m)], 4, m)
        else:
            # a mesh never selects a body with no sharded form (irregular
            # mesh grids run the sharded general one)
            assert m is not None and not AGG.FUSED_BODIES[body].mesh
        # and the builder itself refuses the composition
        spec = AGG.FusedSpec(body, func, epilogue, 1, (), m,
                             (0, 0) if "lanes" in form else None)
        with pytest.raises(NotImplementedError, match="form"):
            AGG._fused_program_jit(spec, (), (), None, None, None)
        return
    if "lanes" not in form:
        g = _gids(one, 2)
        want = _single(body, epilogue, one, g, 2, 0.5, P1)
        got = _single(body, epilogue, block, _gids(block, 2, m), 2, 0.5, P1, m)
        _equal(got, want)
        return
    assert AGG.batch_variant_supported(
        block, func, "hist" if hist else epilogue[0], False, m)
    lanes = _lanes(block, epilogue, m)
    g_max = max(l[3] for l in lanes)
    out = _batched(body, epilogue, block, [l[:3] for l in lanes], g_max, m)
    assert jax.tree_util.tree_leaves(out)[0].shape[0] == AGG._pow2(len(lanes), 2)
    for i, ((_g, qv, params, groups), (g1, *_)) in enumerate(
            zip(lanes, _lanes(one, epilogue))):
        want = _single(body, epilogue, one, g1, groups, qv, params)
        got = jax.tree_util.tree_map(
            lambda x: x[i] if epilogue[0] == "topk" else x[i][:groups], out)
        _equal(got, want)


def test_every_body_the_selection_can_name_exists(blocks, mesh):
    """_grid_variant's ladder, the min/max and Pallas promotions and the
    hist rule, over every grid class, function family and placement: each
    name is a table entry, and between them they reach the whole table."""
    funcs = ["rate", "irate", "deriv", "max_over_time", "min_over_time",
             "quantile_over_time", "last"]
    named = set()
    for kind, (one, sharded) in blocks.items():
        for block, m in ((one, None), (sharded, mesh)):
            for func in funcs:
                for is_delta in (False, True):
                    if kind.startswith("hist"):
                        name, reason = AGG._fused_body(True, block, func,
                                                       is_delta, m)
                    else:
                        variant, _r = AGG._grid_variant(block, func, is_delta)
                        assert variant in AGG.FUSED_BODIES
                        name, reason = AGG._fused_body(False, block, func,
                                                       is_delta, m)
                    body = AGG.FUSED_BODIES[name]
                    assert body.hist == kind.startswith("hist")
                    assert reason in (None, "grid_jitter", "grid_holes")
                    named.add(name)
    assert named == set(AGG.FUSED_BODIES) == set(SELECTS)
    # a window's failing the safety bound degrades inside one family
    for name, body in AGG.FUSED_BODIES.items():
        assert (body.degrade is None) == (
            name.split("_")[0] not in ("jitter", "masked")
            and name != "hist_jitter")
        assert AGG.FUSED_BODIES[
            "hist_general" if body.hist else "general"].degrade is None
