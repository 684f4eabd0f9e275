"""Vectorized posting-bitmap part-key index suite (make test-index).

The bitmap index (memstore/index.py PartKeyIndex + memstore/postings.py)
must return IDENTICAL part-id sets to the retained set-arithmetic oracle
(SetBasedPartKeyIndex) — exact equality, not tolerance — across randomized
filter combinations (eq / in / literal-alternation / prefix regex / general
regex / negative / empty-matcher), interval overlap, and limits; stay
equal under incremental add / update_end_time / remove; and survive
concurrent lookup-vs-ingest.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from filodb_tpu.core.filters import ColumnFilter, equals, regex
from filodb_tpu.memstore.index import PartKeyIndex, SetBasedPartKeyIndex

pytestmark = pytest.mark.index

BIG = 2**62


def make_universe(rng, n=600, sparse_ids=False):
    """Random tag universe: a high-card label, medium labels, an optional
    label (missing-tag semantics), and random [start, end] intervals."""
    parts = []
    used = set()
    for i in range(n):
        if sparse_ids:
            pid = int(rng.integers(0, n * 37))
            while pid in used:
                pid = int(rng.integers(0, n * 37))
        else:
            pid = i
        used.add(pid)
        tags = {
            "_metric_": f"metric_{rng.integers(6)}",
            "host": f"h{rng.integers(80)}",
            "dc": ["us-east", "us-west", "eu", "ap"][rng.integers(4)],
        }
        if rng.random() < 0.4:
            tags["extra"] = f"e{rng.integers(4)}"
        if rng.random() < 0.1:
            tags["rare"] = f"r{rng.integers(2)}"
        start = int(rng.integers(0, 10_000))
        end = int(start + rng.integers(50, 15_000))
        parts.append((pid, tags, start, end))
    return parts


def build_pair(parts):
    bm, oracle = PartKeyIndex(), SetBasedPartKeyIndex()
    for pid, tags, s, e in parts:
        bm.add_partkey(pid, tags, s, e)
        oracle.add_partkey(pid, tags, s, e)
    return bm, oracle


def random_filter(rng) -> ColumnFilter:
    col = ["_metric_", "host", "dc", "extra", "rare", "absent"][rng.integers(6)]
    kind = rng.integers(9)
    if kind == 0:
        return ColumnFilter(col, "=", f"metric_{rng.integers(6)}"
                            if col == "_metric_" else f"h{rng.integers(80)}")
    if kind == 1:  # empty-matcher equality (matches missing tag)
        return ColumnFilter(col, "=", "")
    if kind == 2:
        return ColumnFilter(col, "in", (f"h{rng.integers(80)}",
                                        f"h{rng.integers(80)}", "us-east"))
    if kind == 3:  # literal alternation
        return ColumnFilter(col, "=~", "|".join(
            f"h{rng.integers(80)}" for _ in range(int(rng.integers(1, 4)))))
    if kind == 4:  # prefix regex
        return ColumnFilter(col, "=~", ["h1.*", "us.*", "metric_.*",
                                        "e.*", ""][rng.integers(5)])
    if kind == 5:  # general anchored regex
        return ColumnFilter(col, "=~", ["h[0-7].*", "h1[0-9]", "metric_[0-3]",
                                        "us-(east|west)", ".*st",
                                        ".+"][rng.integers(6)])
    if kind == 6:
        return ColumnFilter(col, "!=", ["h3", "us-east", "e1",
                                        ""][rng.integers(4)])
    if kind == 7:
        return ColumnFilter(col, "!~", ["h1.*", "us.*", ".+", "",
                                        "h[0-4].*"][rng.integers(5)])
    return ColumnFilter(col, "not in", ("h1", "us-east"))


def assert_same_lookup(bm, oracle, filters, s, e, limit=None):
    got = bm.part_ids_from_filters(filters, s, e, limit).tolist()
    want = oracle.part_ids_from_filters(filters, s, e, limit).tolist()
    assert got == want, (filters, s, e, limit)


class TestPropertyEquivalence:
    @pytest.mark.parametrize("seed", range(10))
    def test_random_filter_combos(self, seed):
        rng = np.random.default_rng(seed)
        parts = make_universe(rng, sparse_ids=seed % 3 == 0)
        bm, oracle = build_pair(parts)
        for _ in range(40):
            filters = [random_filter(rng)
                       for _ in range(int(rng.integers(1, 4)))]
            s = int(rng.integers(0, 20_000))
            e = int(s + rng.integers(0, 20_000))
            lim = int(rng.integers(1, 50)) if rng.random() < 0.3 else None
            assert_same_lookup(bm, oracle, filters, s, e, lim)
        # no-filter scan + full-range + label introspection ride along
        assert_same_lookup(bm, oracle, [], 0, BIG)
        assert bm.label_names([], 0, BIG) == oracle.label_names([], 0, BIG)
        f = [equals("_metric_", "metric_1")]
        assert bm.label_names(f, 0, BIG) == oracle.label_names(f, 0, BIG)
        for lbl in ("host", "extra", "absent"):
            assert (bm.label_values([], lbl, 0, BIG)
                    == oracle.label_values([], lbl, 0, BIG))
            assert (bm.label_values(f, lbl, 0, BIG)
                    == oracle.label_values(f, lbl, 0, BIG))
            assert bm.cardinality(lbl) == oracle.cardinality(lbl)

    def test_dense_promotion_stays_equal(self):
        """A value covering most of the universe promotes its container to
        packed words; results must not change."""
        bm, oracle = PartKeyIndex(), SetBasedPartKeyIndex()
        for pid in range(5000):
            tags = {"_ws_": "demo", "host": f"h{pid % 7}"}
            bm.add_partkey(pid, tags, 0, 100)
            oracle.add_partkey(pid, tags, 0, 100)
        ws = bm._labels["_ws_"].containers["demo"]
        ws.finalize(bm._nbits)
        assert ws.words is not None, "expected dense promotion"
        for filters in ([equals("_ws_", "demo")],
                        [equals("_ws_", "demo"), equals("host", "h3")],
                        [ColumnFilter("_ws_", "!=", "other")],
                        [ColumnFilter("host", "=~", "h[0-2]")]):
            assert_same_lookup(bm, oracle, filters, 0, BIG)
            assert_same_lookup(bm, oracle, filters, 0, BIG, limit=17)

    def test_mixed_width_dense_ops(self):
        """Two containers promoted dense at DIFFERENT universe capacities
        (bitmap widths differ) must still AND/OR/ANDNOT correctly — the
        algebra aligns to the widest operand."""
        bm, oracle = PartKeyIndex(), SetBasedPartKeyIndex()
        pid = 0
        for _ in range(3000):  # value A promotes at a small universe
            for idx in (bm, oracle):
                idx.add_partkey(pid, {"grp": "A", "host": f"h{pid % 5}"}, 0)
            pid += 1
        # force A's finalize (and dense promotion) at the SMALL capacity
        assert_same_lookup(bm, oracle, [equals("grp", "A")], 0, BIG)
        for _ in range(30000):  # universe grows ~10x; B promotes wider
            for idx in (bm, oracle):
                idx.add_partkey(pid, {"grp": "B", "host": f"h{pid % 5}"}, 0)
            pid += 1
        ca = bm._labels["grp"].containers["A"]
        cb = bm._labels["grp"].containers["B"]
        ca.finalize(bm._nbits)
        cb.finalize(bm._nbits)
        assert ca.words is not None and cb.words is not None
        assert len(ca.words) != len(cb.words)
        for filters in (
            [ColumnFilter("grp", "=~", "A|B")],        # dense OR dense
            [equals("grp", "A"), equals("grp", "B")],  # dense AND dense
            [ColumnFilter("grp", "!=", "A")],          # tagged ANDNOT dense
            [equals("grp", "B"), equals("host", "h2")],
        ):
            assert_same_lookup(bm, oracle, filters, 0, BIG)

    def test_missing_tag_semantics(self):
        """f.matches(None) rule: {k!=\"v\"}, {k=~\".*\"}, {k=\"\"} match
        series missing k entirely — one `all &~ tagged` bitmap op."""
        bm, oracle = build_pair([
            (0, {"a": "x"}, 0, 100),
            (1, {"a": "y", "b": "q"}, 0, 100),
            (2, {"b": "q"}, 0, 100),
        ])
        for f in (ColumnFilter("a", "!=", "x"),
                  ColumnFilter("a", "=~", ".*"),
                  ColumnFilter("a", "=~", "x*"),
                  ColumnFilter("a", "=", ""),
                  ColumnFilter("a", "!~", "x"),
                  ColumnFilter("a", "!~", ".+"),
                  ColumnFilter("c", "=~", ".*"),
                  ColumnFilter("c", "!=", "anything")):
            assert_same_lookup(bm, oracle, [f], 0, BIG)


class TestIncrementalParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_add_update_remove_script(self, seed):
        """Random interleaving of add_partkey / update_end_time / remove,
        equality re-checked after every mutation burst."""
        rng = np.random.default_rng(100 + seed)
        bm, oracle = PartKeyIndex(), SetBasedPartKeyIndex()
        live: list[int] = []
        next_pid = 0
        probes = [
            [equals("_metric_", "metric_2")],
            [regex("host", "h1.*")],
            [ColumnFilter("host", "!~", "h[0-3].*")],
            [ColumnFilter("extra", "=", "")],
            [equals("_metric_", "metric_1"), regex("dc", "us.*")],
        ]
        for _ in range(30):
            op = rng.random()
            if op < 0.55 or not live:
                for _ in range(int(rng.integers(1, 40))):
                    tags = {
                        "_metric_": f"metric_{rng.integers(4)}",
                        "host": f"h{rng.integers(30)}",
                        "dc": ["us-east", "us-west", "eu"][rng.integers(3)],
                    }
                    if rng.random() < 0.3:
                        tags["extra"] = f"e{rng.integers(3)}"
                    s = int(rng.integers(0, 5000))
                    bm.add_partkey(next_pid, tags, s)
                    oracle.add_partkey(next_pid, tags, s)
                    live.append(next_pid)
                    next_pid += 1
            elif op < 0.8:
                for pid in rng.choice(live, size=min(len(live), 10),
                                      replace=False):
                    end = int(rng.integers(1000, 9000))
                    bm.update_end_time(int(pid), end)
                    oracle.update_end_time(int(pid), end)
            else:
                drop = [int(p) for p in rng.choice(
                    live, size=min(len(live), int(rng.integers(1, 20))),
                    replace=False)]
                bm.remove(drop)
                oracle.remove(drop)
                live = [p for p in live if p not in set(drop)]
            for filters in probes:
                s = int(rng.integers(0, 8000))
                assert_same_lookup(bm, oracle, filters, s, s + 3000)
                assert_same_lookup(bm, oracle, filters, 0, BIG)
            assert len(bm) == len(oracle)
            # label introspection stays in lockstep through removals too
            assert bm.label_names([], 0, BIG) == oracle.label_names([], 0, BIG)
            assert (bm.label_values([], "extra", 0, BIG)
                    == oracle.label_values([], "extra", 0, BIG))

    def test_remove_then_readd_same_id(self):
        bm, oracle = build_pair([(7, {"a": "x", "b": "y"}, 0, 50)])
        for idx in (bm, oracle):
            idx.remove([7])
            idx.add_partkey(7, {"a": "z"}, 10, 60)
        assert_same_lookup(bm, oracle, [equals("a", "z")], 0, BIG)
        assert_same_lookup(bm, oracle, [equals("a", "x")], 0, BIG)
        assert_same_lookup(bm, oracle, [ColumnFilter("b", "=", "")], 0, BIG)


class TestConcurrentSoak:
    def test_lookup_vs_ingest(self):
        """Lookup threads hammer the index while an ingest thread keeps
        adding (and occasionally removing) parts: no exceptions, every
        result sorted-unique, and the final state equals the oracle."""
        bm = PartKeyIndex()
        oracle = SetBasedPartKeyIndex()
        stop = threading.Event()
        errors: list = []
        filters_pool = [
            [equals("_metric_", "metric_1")],
            [regex("host", "h2.*")],
            [ColumnFilter("host", "!~", "h[0-4].*")],
            [equals("_metric_", "metric_0"), regex("host", "h1|h2|h3")],
        ]

        def looker(k):
            i = 0
            try:
                while not stop.is_set():
                    f = filters_pool[(i + k) % len(filters_pool)]
                    out = bm.part_ids_from_filters(f, 0, BIG)
                    arr = out.tolist()
                    assert arr == sorted(set(arr))
                    bm.label_values([], "host", 0, BIG)
                    i += 1
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=looker, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        rng = np.random.default_rng(0)
        added = []
        try:
            for pid in range(4000):
                tags = {"_metric_": f"metric_{pid % 3}",
                        "host": f"h{rng.integers(50)}"}
                bm.add_partkey(pid, tags, 0)
                oracle.add_partkey(pid, tags, 0)
                added.append((pid, tags))
                if pid % 500 == 499:
                    drop = [p for p, _ in added[:20]]
                    bm.remove(drop)
                    oracle.remove(drop)
                    added = added[20:]
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors, errors[:1]
        for f in filters_pool:
            assert_same_lookup(bm, oracle, f, 0, BIG)

