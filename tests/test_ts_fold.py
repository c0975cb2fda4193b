"""The (group, bucket) fold of the `timeseries` program (ops/timeseries):
the request's accumulator shape decides how it runs -- a dense histogram
at or under DENSE_MAX_CELLS, a segment scatter above -- and the answer is
the same either way, and the numpy twin's: counts and value counts bit
for bit, min / max exactly, sums within the engines' tolerance. Both
sides of the threshold, dropped spans (gid -1), spans outside the
window, an empty mask, every span in one cell, bucket counts that are
no power of two. `acc_shape` is what db/metrics_exec caps, and each
launch writes one `ts_fold` routing row. CPU, tiny data."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from tempo_tpu.db import metrics_exec
from tempo_tpu.ops import timeseries as ts
from tempo_tpu.ops.device import bucket, pad_rows
from tempo_tpu.ops.filter import T_SPAN, Cond, Operands
from tempo_tpu.util.kerneltel import TEL

N_SPANS, S_B, R_B, T_B = 1500, 2048, 1024, 1024
STEP_MS = 100
QUERY = (("cond", 0), (Cond(T_SPAN, "span.kind", "eq"),))

# (n_groups, n_buckets): 1 x 64 as `rate_service` asks, sizes that are no
# power of two, the largest dense shapes and their scatter neighbours
GRID = [(1, 60), (1, 61), (3, 8), (7, 100), (64, 64), (65, 64), (64, 65),
        (1, 4096), (2, 4000), (130, 9)]
CASES = ["mixed", "empty_mask", "one_cell"]


def _data(case: str, n_groups: int, n_buckets: int):
    """Raw host columns, group ids, values and the condition's operand."""
    rng = np.random.default_rng(n_groups * 8191 + n_buckets)
    # a tenth of the spans start before the window, a tenth after it
    start = rng.integers(-n_buckets * STEP_MS // 8, n_buckets * STEP_MS * 9 // 8,
                         N_SPANS).astype(np.int32)
    gid = rng.integers(-1, n_groups, N_SPANS).astype(np.int32)  # -1 drops the span
    kind = rng.integers(0, 3, N_SPANS).astype(np.int32)
    want_kind = 1
    if case == "empty_mask":
        want_kind = 7
    elif case == "one_cell":
        start[:] = (n_buckets - 1) * STEP_MS + 5
        gid[:] = n_groups - 1
        kind[:] = 1
    val = (rng.integers(-50, 50, N_SPANS) / 4).astype(np.float32)
    pres = rng.random(N_SPANS) < 0.8
    cols = {"span.start_ms": start, "span.kind": kind}
    return cols, gid, val, pres, Operands.build([(0, want_kind, 0, 0.0, 0.0)])


def _staged(cols):
    padded = {n: pad_rows(a, S_B, np.int32(-(2**31))) for n, a in cols.items()}
    return SimpleNamespace(cols=dict(zip(padded, jax.device_put(list(padded.values())))),
                           n_spans=N_SPANS, n_spans_b=S_B, n_res_b=R_B, n_traces_b=T_B)


def _program_at(G_b, B_b, staged, operands, gid, val, pres, n_groups, n_buckets):
    """The compiled program at a padded shape of the caller's choosing:
    how a request under the threshold is folded by the other engine."""
    fn = ts._compiled_ts(*QUERY, (), val is not None, S_B, R_B, T_B, G_b, B_b)
    empty = np.zeros(0, np.float32)
    outs = fn(staged.cols, operands.ints, operands.floats, [],
              pad_rows(gid, S_B, np.int32(-1)),
              empty if val is None else pad_rows(val, S_B, np.float32(0)),
              empty if val is None else pad_rows(pres, S_B, False),
              np.int32(0), np.int32(STEP_MS), np.int32(N_SPANS), np.int32(n_buckets))
    return tuple(np.asarray(o)[:n_groups, :n_buckets] for o in outs)


def _assert_same(got, want, host: bool):
    names = ("counts", "vcnt", "vsum", "vmin", "vmax")
    for name, g, w in zip(names, got, want):
        if name == "vsum":
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4, err_msg=name)
        elif host and name in ("vmin", "vmax"):  # the twin folds in f64
            np.testing.assert_array_equal(g, w.astype(np.float32), err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("has_val", [False, True], ids=["count", "value"])
@pytest.mark.parametrize("shape", GRID, ids=[f"{g}x{b}" for g, b in GRID])
def test_dense_scatter_and_host_agree(shape, has_val, case):
    n_groups, n_buckets = shape
    cols, gid, val, pres, operands = _data(case, n_groups, n_buckets)
    if not has_val:
        val = pres = None
    want = ts.eval_timeseries_host(QUERY, cols, operands, N_SPANS, 1, gid, val, pres,
                                   0, STEP_MS, n_buckets, n_groups)
    if case == "empty_mask":
        assert want[0].sum() == 0
    elif case == "one_cell":
        assert want[0][-1, -1] == N_SPANS == want[0].sum()
    else:
        assert 0 < want[0].sum() < N_SPANS // 2
    staged = _staged(cols)
    got = ts.eval_timeseries_device(QUERY, staged, operands, gid, val, pres,
                                    0, STEP_MS, n_buckets, n_groups)
    assert got[0].dtype == np.int32
    _assert_same(got, want, host=True)
    # the other engine on the same request: the scatter at bucket()'s
    # padding, what every request ran before the shape followed it
    G_b, B_b = bucket(n_groups), bucket(n_buckets)
    assert ts.fold_route(G_b * B_b) == ("scatter", "large_acc")
    other = _program_at(G_b, B_b, staged, operands, gid, val, pres, n_groups, n_buckets)
    _assert_same(got, other, host=False)


DENSE_SHAPES = [(1, 64), (1, 128), (4, 64), (64, 64), (1, 4096), (2, 2048)]


@pytest.mark.parametrize("shape", DENSE_SHAPES, ids=[f"{g}x{b}" for g, b in DENSE_SHAPES])
def test_threshold_shapes_fold_densely(shape):
    """Every padded shape acc_shape hands out under the threshold is its
    own fixed point and takes the dense engine; one more cell scatters."""
    g, b = shape
    assert ts.acc_shape(g, b) == (g, b)
    assert ts.fold_route(g * b) == ("dense", "small_acc")
    assert g * b <= ts.DENSE_MAX_CELLS
    over = ts.acc_shape(g, ts.DENSE_MAX_CELLS // g + 1)
    assert ts.fold_route(over[0] * over[1]) == ("scatter", "large_acc")


@pytest.mark.parametrize("n_groups,n_buckets,want", [
    (0, 0, (1, 64)), (1, 60, (1, 64)), (1, 61, (1, 64)), (1, 65, (1, 128)),
    (3, 8, (4, 64)), (64, 64, (64, 64)), (65, 64, (1024, 1024)),
    (64, 65, (1024, 1024)), (1, 4096, (1, 4096)), (1, 4097, (1024, 8192)),
    (1000, 1000, (1024, 1024)), (4096, 1, (4096, 1024)),
    (1024, 4096, (1024, 4096)), (1025, 4096, (2048, 4096)),
    (4096, 1024, (4096, 1024)), (4096, 1025, (4096, 2048)),
])
def test_acc_shape_is_what_check_cardinality_caps(n_groups, n_buckets, want):
    """Under the threshold powers of two from 1 x 64, past it bucket()'s
    shapes exactly; db/metrics_exec refuses a request iff that shape
    passes MAX_ACC_CELLS -- one function, so the two cannot drift."""
    shape = ts.acc_shape(n_groups, n_buckets)
    assert shape == want
    if shape[0] * shape[1] > ts.DENSE_MAX_CELLS:
        assert shape == (bucket(n_groups), bucket(n_buckets))
    if shape[0] * shape[1] > metrics_exec.MAX_ACC_CELLS:
        with pytest.raises(ValueError, match="cardinality too high"):
            metrics_exec._check_cardinality(n_groups, n_buckets)
    else:
        metrics_exec._check_cardinality(n_groups, n_buckets)


@pytest.mark.parametrize("shape,row", [
    ((1, 60), ("dense", "small_acc")), ((64, 64), ("dense", "small_acc")),
    ((65, 64), ("scatter", "large_acc")), ((1, 4097), ("scatter", "large_acc")),
], ids=["1x60", "64x64", "65x64", "1x4097"])
def test_each_launch_writes_one_ts_fold_row(shape, row):
    """`ts_fold` in /status/kernels `routing`: one row a `timeseries`
    launch, the engine the accumulator's shape selects."""
    n_groups, n_buckets = shape
    cols, gid, _, _, operands = _data("mixed", n_groups, n_buckets)
    staged = _staged(cols)

    def rows():
        return {k[1:]: n for k, n in TEL.routing_counts().items() if k[0] == "ts_fold"}

    before = rows()
    for _ in range(2):
        ts.eval_timeseries_device(QUERY, staged, operands, gid, None, None,
                                  0, STEP_MS, n_buckets, n_groups)
    after = rows()
    assert after.get(row, 0) - before.get(row, 0) == 2
    assert sum(after.values()) - sum(before.values()) == 2
