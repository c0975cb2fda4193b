"""The `timeseries` program compiled for the chip the cells run on, at the
cells' size, without the chip (the TPU compiler is installed here and
compiles for a described v5e). What a jaxpr cannot show: that the dense
fold's `[cells, rows]` compare never becomes an array -- the compiler
fuses compare, select and reduce, and the program's temporaries stay a
few span-length columns. Nothing runs, so this says nothing about time.
One file, one fixture: only the worker that gets this file loads the
TPU's library (see the on-chip-measurement guide, section 2)."""

from __future__ import annotations

import os

import jax
import numpy as np
import pytest

from tempo_tpu.ops.filter import T_RES, Cond
from tempo_tpu.ops.timeseries import _compiled_ts

S_B, R_B, T_B = 1 << 24, 1 << 10, 1 << 18  # chip1-4block, a whole block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_rate_service(one_chip, has_val: bool, G_b: int, B_b: int):
    """`{ resource.service.name = "x" } | rate()` (or a value fold) over
    one whole staged block at a padded accumulator shape."""
    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = lambda *shape: arr(np.int32, *shape)  # noqa: E731
    cols = {"span@res.service_id": i32(S_B), "span.start_ms": i32(S_B)}
    fn = _compiled_ts(("cond", 0), (Cond(T_RES, "res.service_id", "eq"),), (),
                      has_val, S_B, R_B, T_B, G_b, B_b)
    val, pres = ((arr(np.float32, S_B), arr(np.bool_, S_B)) if has_val
                 else (arr(np.float32, 0),) * 2)
    return fn.lower(cols, i32(1, 3), arr(np.float32, 1, 2), [], i32(S_B),
                    val, pres, i32(), i32(), i32(), i32()).compile()


@pytest.mark.parametrize("has_val,shape", [(False, (1, 64)), (True, (1, 64)),
                                           (False, (64, 64))],
                         ids=["count-1x64", "value-1x64", "count-64x64"])
def test_dense_fold_compiles_for_v5e_without_a_rows_by_cells_array(
        one_chip, no_compile_cache, has_val, shape):
    compiled = _compile_rate_service(one_chip, has_val, *shape)
    column = S_B * 4  # one span-length int32 column: 64 MiB
    # `seg` and the weights between the scan's fusion and each reduce:
    # a handful of columns; [cells, rows] would be 64 or 4,096 of them
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 * column
    text = compiled.as_text()
    assert "scatter" not in text and "gather" not in text and "sort(" not in text


@pytest.mark.parametrize("k,rung", [(16384, 4), (131072, 4)], ids=["attr-4", "tag-4"])
def test_group_select_compiles_for_v5e_at_the_hourly_blocks_bucket(
        one_chip, no_compile_cache, k, rung):
    """The cross-block select of `chip1-range-mix` (PR 34): `rung` slots of
    a 32,768-trace bucket (an hourly block of 18,750 traces), at the k of a
    `limit` 5000 and of a tag search that asks for every match (k = every
    row the group holds). ~15 s a compile: the sort behind `top_k`."""
    from tempo_tpu.ops.select import _compiled_select_group

    part = 1 << 15

    def parts(dtype):
        return tuple(jax.ShapeDtypeStruct((part,), dtype, sharding=one_chip)
                     for _ in range(rung))

    fn = _compiled_select_group(k, rung, part)
    compiled = fn.lower(parts(np.bool_), parts(np.int32), parts(np.int32)).compile()
    # the answer (3k + 1 words) and a few copies of the group's rows
    assert compiled.memory_analysis().temp_size_in_bytes <= 64 * rung * part
