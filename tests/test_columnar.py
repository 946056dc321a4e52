"""Columnar substrate: property tests (hypothesis) + numpy oracles."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.columnar import ColumnTable, compute, utf8_column
from repro.columnar.table import (Column, concat_tables, numeric_column,
                                   pack_validity)


# -- strategies ----------------------------------------------------------------

_names = st.sampled_from(["alpha", "beta", "gamma", "delta"])


@st.composite
def tables(draw, max_rows=40):
    n = draw(st.integers(0, max_rows))
    n_num = draw(st.integers(1, 3))
    data = {}
    for i in range(n_num):
        data[f"num{i}"] = draw(st.lists(
            st.floats(-1e6, 1e6, allow_nan=False), min_size=n, max_size=n))
    data["key"] = draw(st.lists(_names, min_size=n, max_size=n))
    return ColumnTable.from_pydict(data)


# -- zero-copy invariants ---------------------------------------------------


@given(tables())
@settings(max_examples=30, deadline=None)
def test_projection_is_zero_copy(t):
    cols = t.column_names[:2]
    p = t.project(cols)
    for c in cols:
        assert p.column(c) is t.column(c)      # same Column object
        assert p.column(c).data is t.column(c).data


def test_with_column_shares_untouched_buffers():
    t = ColumnTable.from_pydict({"a": [1.0, 2.0], "b": [3.0, 4.0]})
    t2 = t.with_column("c", np.array([5.0, 6.0]))
    assert t2.column("a").data is t.column("a").data


# -- filter / sort / groupby vs numpy ------------------------------------------


@given(tables())
@settings(max_examples=30, deadline=None)
def test_filter_matches_numpy(t):
    if t.num_rows == 0:
        return
    expr = compute.parse_predicate("num0 > 0") if "num0" in t else None
    got = compute.filter_table(t, "num0 > 0")
    vals = np.asarray(t.column("num0").to_numpy())
    assert got.num_rows == int((vals > 0).sum())


@given(tables())
@settings(max_examples=30, deadline=None)
def test_groupby_sum_matches_numpy(t):
    if t.num_rows == 0:
        return
    got = compute.group_by(t, ["key"], {"s": ("num0", "sum"),
                                        "n": ("num0", "count")})
    keys = np.asarray(t.column("key").to_numpy(), dtype=object)
    vals = np.asarray(t.column("num0").to_numpy())
    for k, s, n in zip(got.column("key").to_numpy(),
                       got.column("s").to_numpy(),
                       got.column("n").to_numpy()):
        mask = keys == k
        np.testing.assert_allclose(s, vals[mask].sum(), rtol=1e-9)
        assert n == mask.sum()


@given(tables())
@settings(max_examples=20, deadline=None)
def test_sort_by(t):
    if t.num_rows == 0:
        return
    s = compute.sort_by(t, ["num0"])
    vals = np.asarray(s.column("num0").to_numpy())
    assert np.all(np.diff(vals) >= 0)


@given(tables())
@settings(max_examples=15, deadline=None)
def test_concat_preserves_rows(a):
    b = a.slice(0, a.num_rows // 2)
    c = concat_tables([a, b])
    assert c.num_rows == a.num_rows + b.num_rows
    for n in a.column_names:
        assert c.column(n).to_pylist() == (a.column(n).to_pylist()
                                           + b.column(n).to_pylist())


# -- joins, nulls, slices --------------------------------------------------------


def test_hash_join_inner_and_left():
    left = ColumnTable.from_pydict({"k": ["a", "b", "c"], "x": [1, 2, 3]})
    right = ColumnTable.from_pydict({"k": ["b", "c", "c"], "y": [9, 8, 7]})
    inner = compute.hash_join(left, right, ["k"])
    assert inner.to_pydict() == {"k": ["b", "c", "c"], "x": [2, 3, 3],
                                 "y": [9, 8, 7]}
    left_j = compute.hash_join(left, right, ["k"], how="left")
    assert left_j.num_rows == 4
    assert left_j.column("y").to_pylist()[-1] is None


def test_null_handling():
    c = numeric_column([1.0, 2.0, 3.0], validity=[True, False, True])
    assert c.null_count == 1
    assert c.to_pylist() == [1.0, None, 3.0]
    u = utf8_column(["hi", None, "yo"])
    assert u.null_count == 1
    assert u.to_pylist() == ["hi", None, "yo"]


def test_slice_is_view_for_numeric():
    t = ColumnTable.from_pydict({"a": np.arange(10.0)})
    s = t.slice(2, 5)
    assert s.num_rows == 5
    assert s.column("a").data.base is not None   # numpy view


def test_utf8_roundtrip_unicode():
    vals = ["héllo", "wörld", "日本語", ""]
    c = utf8_column(vals)
    assert list(c.to_numpy()) == vals


# -- utf8 gather against a per-row reference -------------------------------------


def _take_rowwise(col, indices):
    """Plain per-row utf8 gather: the reference for `Column.take`."""
    n = col.num_rows
    rows = [int(i) + n if i < 0 else int(i) for i in indices]
    off, mask = col.offsets, col.valid_mask()
    chunks = [col.data[off[i]:off[i + 1]].tobytes() for i in rows]
    offsets = np.zeros(len(rows) + 1, dtype=np.int32)
    np.cumsum([len(b) for b in chunks], out=offsets[1:])
    data = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    validity = (None if col.validity is None
                else pack_validity(np.array([mask[i] for i in rows], bool)))
    values = col.to_pylist()
    return data, offsets, validity, [values[i] for i in rows]


def _q1_flags(n=1000, seed=7):
    # as the TPC-H Q1 configuration builds l_returnflag: one byte per row
    codes = np.random.default_rng(seed).choice(
        np.frombuffer(b"ANR", np.uint8), n)
    return Column("utf8", codes, np.arange(n + 1))


_UTF8_TAKE_CASES = {
    "slice_offsets_not_at_zero": (
        lambda: utf8_column(["ab", "cde", "", "f", "ghij", "kl"]).slice(2, 3),
        [2, 0, 1, 1]),
    "empty_indices": (lambda: utf8_column(["a", "bc"]), []),
    "empty_strings": (lambda: utf8_column(["", "", "x", ""]), [0, 1, 3, 2]),
    "multi_byte": (lambda: utf8_column(["日本語", "é", "a", "wörld"]),
                   [0, 3, 2, 1, 0]),
    "repeated_unsorted_negative": (
        lambda: utf8_column(["aa", "b", "ccc", "dd"]), [3, -1, 0, 0, -4, 2, -2]),
    "nulls": (lambda: utf8_column(["hi", None, "yo", None, "日"]),
              [1, 2, 4, 3, 0, 1]),
    "nulls_in_slice": (
        lambda: utf8_column(["x", None, "yz", "", None, "w"]).slice(1, 4),
        [3, 0, 2, -3]),
    "q1_flags": (_q1_flags,
                 np.nonzero(np.random.default_rng(3).random(1000) < 0.97)[0]),
}


@pytest.mark.parametrize("case", sorted(_UTF8_TAKE_CASES))
def test_utf8_take_matches_rowwise_reference(case):
    make, indices = _UTF8_TAKE_CASES[case]
    col = make()
    indices = np.asarray(indices, dtype=np.int64)
    got = col.take(indices)
    data, offsets, validity, values = _take_rowwise(col, indices)
    assert got.data.dtype == np.uint8 and got.offsets.dtype == np.int32
    np.testing.assert_array_equal(got.data, data)
    np.testing.assert_array_equal(got.offsets, offsets)
    if validity is None:
        assert got.validity is None
    else:
        np.testing.assert_array_equal(got.validity, validity)
    assert got.to_pylist() == values


def test_filter_mixed_table_matches_rowwise_reference():
    n = 500
    rng = np.random.default_rng(11)
    words = ["", "a", "bb", "日本語", None, "é"]
    t = ColumnTable({
        "x": numeric_column(rng.normal(size=n)),
        "i": numeric_column(np.arange(n, dtype=np.int32)),
        "flag": _q1_flags(n, seed=5),
        "s": utf8_column([words[k] for k in rng.integers(0, len(words), n)]),
    }).slice(17, 400)
    mask = t.column("x").data > -0.5
    got = t.filter(mask)
    rows = np.nonzero(mask)[0]
    np.testing.assert_array_equal(got.column("x").data, t.column("x").data[rows])
    np.testing.assert_array_equal(got.column("i").data, t.column("i").data[rows])
    for name in ("flag", "s"):
        data, offsets, validity, values = _take_rowwise(t.column(name), rows)
        c = got.column(name)
        np.testing.assert_array_equal(c.data, data)
        np.testing.assert_array_equal(c.offsets, offsets)
        assert (c.validity is None) == (validity is None)
        if validity is not None:
            np.testing.assert_array_equal(c.validity, validity)
        assert c.to_pylist() == values
