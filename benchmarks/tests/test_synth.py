import numpy as np

from benchmarks.lib.synth import synth_columns


def test_copied_generator_is_byte_equal_to_the_programs():
    """For as long as the program keeps its own: the benchmark's copy draws
    the same columns from the same seed."""
    from tempo_tpu.util.testdata import synth_columns as original

    mine = synth_columns(np.random.default_rng([5, 1]), 60, 5, base_time_ns=10**18)
    theirs = original(np.random.default_rng([5, 1]), 60, 5, base_time_ns=10**18)
    assert mine[1] == theirs[1]
    assert np.array_equal(mine[2], theirs[2])
    assert mine[0].keys() == theirs[0].keys()
    for k in theirs[0]:
        assert mine[0][k].dtype == theirs[0][k].dtype, k
        assert mine[0][k].tobytes() == theirs[0][k].tobytes(), k


def test_oracle_round_trip(tmp_path):
    from benchmarks.lib.oracle import BlockOracle, load_oracle, save_oracle

    cols, strings, ids = synth_columns(np.random.default_rng([5, 2]), 80, 6,
                                       base_time_ns=10**18)
    save_oracle(str(tmp_path), cols, strings, ids, 6, 2)
    a = BlockOracle(cols, strings, ids, 6)
    b = load_oracle(str(tmp_path))
    assert a.traces_attr("attr.key003", "value-00007") == b.traces_attr("attr.key003", "value-00007")
    assert a.traces_duration_gt(500_000) == b.traces_duration_gt(500_000)
    assert a.traces_service("svc-001") == b.traces_service("svc-001")
    assert a.trace_spans(3) == b.trace_spans(3)
    assert (a.start_s, a.end_s) == (b.start_s, b.end_s)
