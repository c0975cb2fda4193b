"""A generic span-attribute condition gives the same answer however many
dense planes its slice was staged with (ops/stage._assemble: K planes
the kernel ORs over, then overflow rows it scatters): the rule's K, no
plane at all, one, and every row in a plane agree with a caller's flat
rows behind `sattr.off` and with the numpy twin over raw columns
(ops/hostfilter) on the trace mask and on every trace's matched-span
count, for every op and value kind; `{ span.k = v } | rate()` folds the
same span mask. And at the benchmark's buckets the slot-major programs
hold no span-length gather, scatter or cumsum. And the placement itself
(ops/stage._SlotLayout, native pass and numpy fallback alike) stages the
bytes the per-row scatter it replaced staged, which is kept here as its
independent twin. CPU, tiny data; the one real-size test traces abstract
values only."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from tempo_tpu import native
from tempo_tpu.ops import stage
from tempo_tpu.ops.device import PAD_I32, bucket, pad_rows
from tempo_tpu.ops.filter import (
    OPS, T_SATTR, T_SPAN, T_TRACE, Cond, Operands, _compiled, eval_block,
    normalize_tree,
)
from tempo_tpu.ops.hostfilter import eval_block_host
from tempo_tpu.ops.timeseries import (
    _compiled_ts, eval_timeseries_device, eval_timeseries_host,
)
from tempo_tpu.util.kerneltel import TEL

K = 4  # the most attribute rows a span of the crafted block owns
KEY = 1  # the key code the conditions ask for
GROUPS = [300, 1100, 400]  # span rows a row group; the shard is the middle one
SLICES = {"whole": [0, 1, 2], "shard": [1]}
_I32_MAX = 2**31 - 1


def _crafted_block():
    """Raw columns of a block whose spans own 0..K attribute rows: spans
    with none, a key repeated on one span (both rows hitting, one
    hitting, none hitting), clamped ints, every value kind."""
    rng = np.random.default_rng(29)
    n_spans = sum(GROUPS)
    per_trace = rng.integers(5, 60, 80)
    per_trace = per_trace[np.cumsum(per_trace) <= n_spans]
    per_trace[-1] += n_spans - per_trace.sum()
    n_traces = len(per_trace)
    span_off = np.concatenate([[0], np.cumsum(per_trace)]).astype(np.int32)
    cnt = rng.choice(K + 1, n_spans, p=[0.15, 0.15, 0.2, 0.2, 0.3])
    cnt[[0, 299, 300, 1399, 1400, n_spans - 1]] = [0, K, 0, K, 2, 0]  # slice edges
    owners = np.repeat(np.arange(n_spans), cnt).astype(np.int32)
    n_rows = owners.shape[0]
    key = rng.integers(0, 3, n_rows).astype(np.int32)
    vt = rng.integers(0, 4, n_rows).astype(np.int32)
    ints = rng.integers(-3, 4, n_rows).astype(np.int32)
    ints[rng.random(n_rows) < 0.05] = _I32_MAX
    ints[rng.random(n_rows) < 0.05] = -_I32_MAX
    # repeated keys: every row of a span with K rows asks for KEY, values
    # as drawn (so some spans hit twice, some once, some never)
    full = np.flatnonzero(cnt == K)[::3]
    key[np.isin(owners, full)] = KEY
    cols = {
        "span.trace_sid": np.repeat(np.arange(n_traces), per_trace).astype(np.int32),
        "span.start_ms": rng.integers(0, 8000, n_spans).astype(np.int32),
        "trace.span_off": span_off,
        "sattr.span": owners,
        "sattr.key_id": key,
        "sattr.vtype": vt,
        "sattr.str_id": np.where(vt == 0, rng.integers(0, 6, n_rows), -1).astype(np.int32),
        "sattr.int32": np.where(vt == 3, np.abs(ints) % 2, np.where(vt == 1, ints, 0)).astype(np.int32),
        "sattr.f32": np.where(vt == 2, rng.integers(-3, 4, n_rows) / 2, 0).astype(np.float32),
    }
    span_offsets = np.concatenate([[0], np.cumsum(GROUPS)]).tolist()
    attr_offsets = np.searchsorted(owners, span_offsets).tolist()
    blk = SimpleNamespace(
        pack=SimpleNamespace(axes={"span": SimpleNamespace(
            offsets=span_offsets, n_groups=len(GROUPS), n_rows=n_spans)}),
        meta=SimpleNamespace(total_traces=n_traces, block_id="crafted-block"))
    return blk, cols, attr_offsets


_BLOCK = _crafted_block()


def _bounds(groups):
    """(first, end) span row and (first, end) attribute row of a slice."""
    blk, _, attr_offsets = _BLOCK
    so = blk.pack.axes["span"].offsets
    return (so[groups[0]], so[groups[-1] + 1],
            attr_offsets[groups[0]], attr_offsets[groups[-1] + 1])


def _slice_host(groups):
    """The slice's raw columns as db/search._host_cols hands them to the
    numpy evaluator: owners and span offsets rebased to the slice."""
    lo, hi, alo, ahi = _bounds(groups)
    out = {}
    for n, a in _BLOCK[1].items():
        if n.startswith("span."):
            out[n] = a[lo:hi]
        elif n.startswith("sattr."):
            out[n] = a[alo:ahi] - (lo if n == "sattr.span" else 0)
        else:
            out[n] = (np.clip(a, lo, hi) - lo).astype(np.int32)
    return out, hi - lo


# how many dense planes a slice is staged with: what ops/stage's rule
# gives it (the whole block K, its middle shard fewer, with overflow),
# none (every row an overflow row), one, and as many as the longest span
LAYOUTS = {"rule": None, "no_plane": 0, "one_plane": 1, "all_planes": K}


def _staged(groups, layout: str, monkeypatch):
    """The slice through ops/stage._assemble with the layout's planes,
    or ("flat") as a caller's own flat rows behind `sattr.off`, on the
    device."""
    blk, cols, _ = _BLOCK
    lo, hi, alo, ahi = _bounds(groups)
    host = {n: (a[lo:hi] if n.startswith("span.") else
                a[alo:ahi] if n.startswith("sattr.") else a)
            for n, a in cols.items()}
    if LAYOUTS.get(layout) is not None:
        monkeypatch.setattr(stage, "_head_planes", lambda *a: LAYOUTS[layout])
    view, padded, _ = stage._assemble(blk, stage.plan_stage(list(host)), groups, host, 0)
    if layout == "flat":
        rows = stage.bucket(ahi - alo)
        cnt = np.bincount(host["sattr.span"] - lo, minlength=view.n_spans_b)
        padded = {n: a for n, a in padded.items() if not n.startswith("sattr.")}
        padded.update({n: stage.pad_rows(host[n], rows, PAD_I32)
                       for n in host if n.startswith("sattr.") and n != "sattr.span"})
        padded["sattr.off"] = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
    else:
        n_over = int(np.count_nonzero(padded["sattr.over"] < view.n_spans_b))
        if layout != "rule":
            want = int(np.maximum(np.bincount(host["sattr.span"]) - LAYOUTS[layout], 0).sum())
            assert n_over == want
        assert padded["sattr.over"].shape[0] == (stage.bucket(n_over) if n_over else 0)
    view.cols = dict(zip(padded, jax.device_put(list(padded.values()))))
    return view


def _operands(kind: str, op: str) -> Operands:
    table = None
    if op in ("intable", "notintable"):
        table = {0: np.array([0, 1, 0, 1, 1, 0, 0, 0], bool)}
    v0, v1 = (2, 4) if kind == "str" else (1, 1) if kind == "bool" else (-1, 2)
    return Operands.build([(KEY, v0, v1, -0.5, 1.0)], table)


_INT_OPS = OPS
_FLOAT_OPS = tuple(o for o in OPS if o not in (
    "intable", "notintable", "ne_clamped", "ne_present"))
CASES = ([(op, k) for k in ("str", "int", "bool") for op in _INT_OPS]
         + [(op, "float") for op in _FLOAT_OPS] + [("exists", "any")])


@pytest.mark.parametrize("slice_name", sorted(SLICES))
@pytest.mark.parametrize("op,kind", CASES, ids=[f"{k}-{op}" for op, k in CASES])
def test_slots_offsets_and_host_agree(op, kind, slice_name, monkeypatch):
    groups = SLICES[slice_name]
    query = (("cond", 0), (Cond(T_SATTR, kind, op, is_float=kind == "float"),))
    operands = _operands(kind, op)
    raw, n_spans = _slice_host(groups)
    n_traces = _BLOCK[0].meta.total_traces
    tm_h, cnt_h = eval_block_host(query, raw, operands, n_spans, n_traces)
    assert op not in ("eq", "exists", "range", "intable") or tm_h.any()
    outs = {}
    for layout in (*LAYOUTS, "flat"):
        st = _staged(groups, layout, monkeypatch)
        assert (st.n_spans, st.n_spans_b) == (n_spans, 2048)  # a padded tail
        sm, tm, cnt = eval_block(
            query, st.cols, operands, st.n_spans, st.n_traces,
            st.n_spans_b, st.n_res_b, st.n_traces_b)
        outs[layout] = tuple(np.asarray(x) for x in (sm, tm, cnt))
        np.testing.assert_array_equal(outs[layout][1][:n_traces], tm_h)
        np.testing.assert_array_equal(outs[layout][2][:n_traces], cnt_h)
    for layout in LAYOUTS:  # padded rows and all
        for a, b in zip(outs[layout], outs["flat"]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("slice_name", sorted(SLICES))
@pytest.mark.parametrize("kind", ["str", "int", "float", "bool", "any"])
def test_rate_folds_the_same_span_mask(kind, slice_name, monkeypatch):
    """`{ span.k = v } | rate()`: device buckets over every layout equal
    the host engine's, group by group."""
    groups = SLICES[slice_name]
    op = "exists" if kind == "any" else "eq"
    query = (("cond", 0), (Cond(T_SATTR, kind, op, is_float=kind == "float"),))
    operands = _operands(kind, op)
    raw, n_spans = _slice_host(groups)
    n_traces = _BLOCK[0].meta.total_traces
    gid = (np.arange(n_spans) % 3).astype(np.int32)
    want, = eval_timeseries_host(query, raw, operands, n_spans, n_traces,
                                 gid, None, None, 0, 1000, 8, 3)
    assert want.sum() > 0
    for layout in (*LAYOUTS, "flat"):
        st = _staged(groups, layout, monkeypatch)
        got, = eval_timeseries_device(query, st, operands, gid, None, None,
                                      0, 1000, 8, 3)
        np.testing.assert_array_equal(got, want)


def test_each_launch_counts_its_reduction(monkeypatch):
    """One `attr_reduce` routing row a launch with an attribute
    condition, none for a launch without; the row says which reduction
    the staged columns select."""
    query = (("cond", 0), (Cond(T_SATTR, "str", "eq"),))
    plain = (("cond", 0), (Cond(T_SPAN, "span.start_ms", "ge"),))
    operands = _operands("str", "eq")

    def launch(q, cols, st):
        eval_block(q, cols, operands, st.n_spans, st.n_traces,
                   st.n_spans_b, st.n_res_b, st.n_traces_b, span_out=False)

    def rows():
        return {k[1:]: n for k, n in TEL.routing_counts().items()
                if k[0] == "attr_reduce"}

    raw, _ = _slice_host(SLICES["whole"])
    for layout, row in (("all_planes", ("slots", "dense_counts")),
                        ("one_plane", ("slots", "skewed_counts")),
                        ("flat", ("offsets", "flat_rows"))):
        st = _staged(SLICES["whole"], layout, monkeypatch)
        before = rows()
        launch(query, st.cols, st)
        launch(plain, st.cols, st)
        eval_timeseries_device(query, st, operands, np.zeros(st.n_spans, np.int32),
                               None, None, 0, 1000, 4, 1)
        after = rows()
        assert after.get(row, 0) - before.get(row, 0) == 2
        assert sum(after.values()) - sum(before.values()) == 2
    # owner rows without offsets: the scatter fallback, and it says so
    flat = {n: v for n, v in st.cols.items() if n != "sattr.off"}
    owners = np.full(flat["sattr.key_id"].shape[0], PAD_I32, np.int32)
    owners[:raw["sattr.span"].shape[0]] = raw["sattr.span"]
    before = rows()
    launch(query, {**flat, "sattr.span": owners}, st)
    assert rows().get(("offsets", "no_offsets"), 0) - before.get(
        ("offsets", "no_offsets"), 0) == 1


# ------------------------------------------- the placement, byte for byte


def _scatter_reference(raw_owners, span_base, n_spans, n_spans_b, cols):
    """The placement as ops/stage._assemble did it until PR 43 -- a
    destination for every row, one fancy-index scatter a column -- ->
    (staged sattr.* columns and `sattr.over`, K, overflow rows)."""
    owners = np.clip(raw_owners - span_base, 0, max(n_spans, 1) - 1)
    cnt = np.bincount(owners, minlength=max(n_spans, 1))
    n_rows = int(owners.shape[0])
    k = min(int(cnt.max()), bucket(max(n_rows, 1)) // n_spans_b)
    slot = np.arange(n_rows, dtype=np.int32) - np.repeat(
        (np.cumsum(cnt) - cnt).astype(np.int32), cnt)
    dest = slot.astype(np.intp) * n_spans_b + owners
    over = np.flatnonzero(slot >= k)
    n_over = int(over.shape[0])
    over_b = bucket(n_over) if n_over else 0
    dest[over] = k * n_spans_b + np.arange(n_over)
    out = {}
    for name, arr in cols.items():
        out[name] = np.full(k * n_spans_b + over_b, PAD_I32, dtype=arr.dtype)
        out[name][dest] = arr
    out["sattr.over"] = np.concatenate(
        [owners[over], np.full(over_b - n_over, n_spans_b, dtype=owners.dtype)])
    return out, k, n_over


def _placement_case(name: str):
    """-> (span rows a row group, the groups staged, raw `sattr.span`,
    value columns): counts per span drawn for the case, owners as the
    block stores them (global span rows, grouped by owner in span order)."""
    rng = np.random.default_rng(43)
    group_spans, groups = [1500], [0]
    cnt = np.full(1500, 2)
    if name == "geometric":  # a long tail: rows beyond K overflow
        cnt = rng.geometric(0.4, 1500) - 1
    elif name == "one_long_span":
        cnt[700] = 40
    elif name == "k0":  # attributes rarer than spans: every row overflows
        cnt = (rng.random(1500) < 0.2).astype(np.int64)
    elif name == "no_rows":
        cnt = np.zeros(1500, np.int64)
    elif name in ("row_group_range", "clipped_edges"):
        group_spans, groups = [400, 900, 300, 600], [1, 2]
        cnt = rng.integers(0, 5, sum(group_spans))
    owners = np.repeat(np.arange(cnt.shape[0]), cnt).astype(np.int32)
    if name == "row_group_range":
        owners = owners[(owners >= 400) & (owners < 1600)]
    elif name == "clipped_edges":  # rows of the spans on either side too
        owners = owners[(owners >= 390) & (owners < 1612)]
    n_rows = owners.shape[0]
    cols = {"sattr.key_id": rng.integers(0, 9, n_rows).astype(np.int32),
            "sattr.int32": rng.integers(-_I32_MAX, _I32_MAX, n_rows).astype(np.int32)}
    if name == "float32":
        cols["sattr.f32"] = rng.normal(size=n_rows).astype(np.float32)
    return group_spans, groups, owners, cols


PLACEMENT_CASES = ["uniform", "geometric", "one_long_span", "k0", "no_rows",
                   "row_group_range", "clipped_edges", "float32"]


def _place(case: str, path: str, monkeypatch):
    """The case through ops/stage._assemble on the native pass or the
    numpy fallback -> (padded columns, real rows, the span's attributes,
    the twin's result)."""
    if path == "native" and not native.available():
        pytest.skip("native library not built")
    if path == "numpy":
        monkeypatch.setattr(native, "slot_place", lambda *a: False)
    group_spans, groups, owners, cols = _placement_case(case)
    offsets = np.concatenate([[0], np.cumsum(group_spans)]).tolist()
    blk = SimpleNamespace(
        pack=SimpleNamespace(axes={"span": SimpleNamespace(
            offsets=offsets, n_groups=len(group_spans), n_rows=offsets[-1])}),
        meta=SimpleNamespace(total_traces=7, block_id="placement-case"))
    host = {"sattr.span": owners, **cols}
    attrs: dict = {}
    view, padded, real_rows = stage._assemble(
        blk, stage.plan_stage(list(host)), groups, host, 0, attrs)
    assert host["sattr.span"] is owners and set(host) == {"sattr.span", *cols}
    assert attrs["path"] == path and attrs["columns"] == len(padded)
    assert attrs["bytes"] == sum(a.nbytes for a in padded.values())
    want = _scatter_reference(owners, view.span_base, view.n_spans,
                              view.n_spans_b, cols)
    return padded, real_rows, attrs, want


@pytest.mark.parametrize("path", ["native", "numpy"])
@pytest.mark.parametrize("case", PLACEMENT_CASES)
def test_placement_equals_the_row_scatter(case, path, monkeypatch):
    """Value columns, `sattr.over`, K and the real row counts of both
    paths against the per-row scatter, to the byte."""
    padded, real_rows, attrs, (want, k, n_over) = _place(case, path, monkeypatch)
    assert set(padded) == set(want)
    n_rows = _placement_case(case)[2].shape[0]
    assert (attrs["rows"], attrs["planes"], attrs["overflow_rows"]) == (n_rows, k, n_over)
    assert (k == 0) == (case in ("k0", "no_rows"))
    assert (n_over > 0) == (case in ("geometric", "one_long_span", "k0",
                                     "row_group_range", "clipped_edges"))
    for name, ref in want.items():
        got = padded[name]
        assert (got.dtype, got.shape) == (ref.dtype, ref.shape), name
        assert got.tobytes() == ref.tobytes(), name
        assert real_rows[name] == (n_over if name == "sattr.over" else n_rows)


@pytest.mark.parametrize("case", PLACEMENT_CASES)
def test_native_pass_equals_numpy_fallback(case, monkeypatch):
    """One layout, each value column through the native pass and through
    the fallback (the library absent: skipped, not failed)."""
    if not native.available():
        pytest.skip("native library not built")
    group_spans, groups, owners, cols = _placement_case(case)
    base = sum(group_spans[:groups[0]])
    n_spans = sum(group_spans[g] for g in groups)
    layout = stage._SlotLayout(owners, base, n_spans, bucket(n_spans))
    for name, arr in cols.items():
        got = layout.place(arr)
        twin = np.empty_like(got)
        layout._place_numpy(arr, twin, arr.dtype.type(PAD_I32))
        assert got.tobytes() == twin.tobytes(), name
    assert layout.numpy_columns == 0
    # descending owners are not a block's: the pass refuses, numpy places
    if owners.shape[0] > 1 and owners[0] != owners[-1]:
        arr = cols["sattr.key_id"]
        out = np.empty(layout.k * layout.n_spans_b + layout.over_b, np.int32)
        assert not native.slot_place(owners[::-1].copy(), base, n_spans,
                                     layout.n_spans_b, layout.k, arr, out, PAD_I32)


@pytest.mark.parametrize("fill", [PAD_I32, 0, -1, np.int32(7), np.float32(0), False],
                         ids=repr)
@pytest.mark.parametrize("shape", [(0,), (5,), (5, 3), (8,), (8, 2)], ids=str)
def test_pad_rows_keeps_its_results(shape, fill):
    """ops/device.pad_rows against the concatenate it was: an array that
    already has n rows comes back itself; 1-D and 2-D; scalar fills."""
    dtype = (np.float32 if isinstance(fill, np.floating)
             else bool if fill is False else np.int32)
    arr = (np.arange(int(np.prod(shape))).reshape(shape) % 5).astype(dtype)
    got = pad_rows(arr, 8, fill)
    assert (got is arr) == (shape[0] == 8)
    want = np.concatenate([arr, np.full((8 - shape[0],) + shape[1:], fill, dtype)])
    assert (got.dtype, got.shape) == (want.dtype, want.shape)
    assert got.tobytes() == want.tobytes()
    assert got.flags.c_contiguous


# ------------------------------------------------ the cells' shapes, abstract

S_B, A_B, T_B, R_B = 1 << 23, 1 << 24, 1 << 18, 1 << 10  # chip1-4block, one shard


def _abstract_cols(over_b: int | None, extra=()):
    """The cells' shard slice: two planes and `over_b` overflow rows
    (0 in every cell: each span owns two rows), or (None) the parent's
    flat rows behind `sattr.off`."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    cols = {n: i32(A_B + (over_b or 0))
            for n in ("sattr.key_id", "sattr.vtype", "sattr.str_id")}
    cols["sattr.off" if over_b is None else "sattr.over"] = i32(
        S_B + 1 if over_b is None else over_b)
    cols.update({"span.trace_sid": i32(S_B), "trace.span_off": i32(T_B + 1),
                 "trace.start_ms": i32(T_B)})
    cols.update({n: i32(S_B) for n in extra})
    return cols


def _span_length_ops(jaxpr, out=None):
    """(primitive, size) of every gather / scatter / cumsum in the
    program, sub-programs included, that moves S_B elements or more:
    what a gather fetches, what a scatter writes, what a cumsum scans
    (tracify's gathers read the 2^23 running counts at 2^18 trace
    offsets: 2^18 elements, not span-length)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        moved = (eqn.outvars[0] if name.startswith("gather") else
                 eqn.invars[2] if name.startswith("scatter") else
                 eqn.invars[0] if name.startswith("cumsum") else None)
        if moved is not None and int(np.prod(moved.aval.shape)) >= S_B:
            out.append((name, int(np.prod(moved.aval.shape))))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _span_length_ops(sub, out)
    return out


def _filter_jaxpr(tree, conds, cols):
    tree = normalize_tree(tree, conds)
    fn = _compiled(tree, conds, (), S_B, R_B, T_B, False)
    i = jax.ShapeDtypeStruct((len(conds), 3), np.int32)
    f = jax.ShapeDtypeStruct((len(conds), 2), np.float32)
    n = jax.ShapeDtypeStruct((), np.int32)
    return jax.make_jaxpr(fn)(cols, i, f, [], n, n).jaxpr


_WINDOW = Cond(T_TRACE, "trace.start_ms", "range")
_PROGRAMS = {
    # { span.k = "v" } inside a window: attr_eq
    "attr_eq": (("and", ("cond", 0), ("cond", 1)),
                (Cond(T_SATTR, "str", "eq"), _WINDOW), ()),
    # { span.k = "v" } >> { duration > x } on a shard: no struct node,
    # the two span sets at trace level (db/search._plan_for_block)
    "struct_desc_shard": (("and", ("cond", 0), ("cond", 1), ("cond", 2)),
                          (Cond(T_SATTR, "str", "eq"),
                           Cond(T_SPAN, "span.dur_us", "gt", needs_verify=True),
                           _WINDOW), ("span.dur_us",)),
}


@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_no_span_length_gather_at_the_cells_buckets(program):
    """What keeps the next refactor from putting them back: at
    n_spans_b 2^23 / 2^24 attribute rows the slot-major program's only
    span-length scan is tracify's cumsum over the span mask, and it
    gathers at trace offsets alone -- with no overflow rows (the cells)
    and with 2^20 of them (what it scatters is those rows, not a span
    axis); the offsets form adds the cumsum over 2^24 attribute rows
    and two gathers of 2^23 indices."""
    tree, conds, extra = _PROGRAMS[program]
    n_tracify = 1 if program == "attr_eq" else 2
    for over_b in (0, 1 << 20):
        jaxpr = _filter_jaxpr(tree, conds, _abstract_cols(over_b, extra))
        slot_ops = _span_length_ops(jaxpr)
        assert [o for o in slot_ops if not o[0].startswith("cumsum")] == []
        assert slot_ops == [("cumsum", S_B)] * len(slot_ops) and len(slot_ops) <= n_tracify + 1
        assert ("scatter" in str(jaxpr)) == bool(over_b)
    flat_ops = _span_length_ops(_filter_jaxpr(tree, conds, _abstract_cols(None, extra)))
    assert sum(o[0] == "gather" for o in flat_ops) == 2
    assert ("cumsum", A_B) in flat_ops


def _rate_jaxpr(has_val: bool, G_b: int, B_b: int):
    """`{ span.k = v } | rate()` (or a value fold) over the cells' shard
    slice at a padded accumulator shape."""
    conds = (Cond(T_SATTR, "str", "eq"),)
    cols = _abstract_cols(0, ("span.start_ms",))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    fn = _compiled_ts(("cond", 0), conds, (), has_val, S_B, R_B, T_B, G_b, B_b)
    val, pres = ((jax.ShapeDtypeStruct((S_B,), np.float32),
                  jax.ShapeDtypeStruct((S_B,), np.bool_)) if has_val
                 else (np.zeros(0, np.float32),) * 2)
    return jax.make_jaxpr(fn)(
        cols, i32(1, 3), jax.ShapeDtypeStruct((1, 2), np.float32), [],
        i32(S_B), val, pres, i32(), i32(), i32(), i32()).jaxpr


def test_rate_program_reduces_attributes_without_a_gather():
    """`{ span.k = v } | rate()` gets the same span mask: at bucket()'s
    1024 x 1024 the slot-major program's only span-length scatter is the
    fold's, the program every request ran before the accumulator's
    shape followed it (ROADMAP A15 ii)."""
    ops = _span_length_ops(_rate_jaxpr(False, 1024, 1024))
    assert [o[0] for o in ops] == ["scatter-add"]


@pytest.mark.parametrize("has_val", [False, True], ids=["count", "value"])
@pytest.mark.parametrize("shape", [(1, 64), (4, 64), (64, 64), (1, 4096)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_small_accumulator_folds_without_a_scatter(shape, has_val):
    """A request-sized accumulator (`rate_service` is 1 x 64) is folded by
    compares and reductions over the span axis: no scatter, gather or
    sort anywhere in the program, and one reduction a statistic."""
    jaxpr = _rate_jaxpr(has_val, *shape)
    assert _span_length_ops(jaxpr) == []
    text = str(jaxpr)
    assert not any(p in text for p in ("scatter", "gather", "sort", "cumsum"))
    n_stats = 5 if has_val else 1
    assert sum(text.count(p) for p in ("reduce_sum", "reduce_min", "reduce_max")) == n_stats
    # the value folds at bucket()'s shape: five scatters, as before
    if has_val and shape == (1, 64):
        big = _span_length_ops(_rate_jaxpr(True, 1024, 1024))
        assert sorted(o[0] for o in big) == sorted(
            ["scatter-add"] * 3 + ["scatter-min", "scatter-max"])
