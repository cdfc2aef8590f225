"""The main path's kernels compile for a v5e chip (no chip attached).

Each test lowers and compiles from ``ShapeDtypeStruct``s for one device
of a described ``v5e:2x2`` topology, at the fact table's capacity
(2^25 rows, TPC-DS SF10 padded) and at the smallest dimension table's
(128 rows).  The topology is described inside a fixture: libtpu may be
loaded by one process at a time, so nothing touches it at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.filter_project.kernel import (filter_scan,
                                                 filter_scan_batch,
                                                 parse_i32)
from repro.relational import expr as E
from repro.relational.physical import (_compact_nz, _fused_fn,
                                       _join_gather_unique, _select_rows,
                                       _sharded_mask_fn, _take_selected)

FACT_CAP = 1 << 25
DIM_CAP = 128

# every opcode family of the postfix program: col-const, col-col over
# mixed dtypes, membership, not/or/and
LITERAL_PROGRAM = (("gt", 0, 50.0), ("ge", 1, 10), ("and",),
                   ("ltc", 0, 1), ("in", 1, (3, 5, 7)), ("not",), ("or",),
                   ("or",))
SLOTTED_PROGRAM = (("gt", 0, ("$f", 0)), ("ge", 1, ("$i", 0)), ("and",),
                   ("ltc", 0, 1), ("or",))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _columns(sharding, cap):
    return (_spec(sharding, (cap,), jnp.float32),
            _spec(sharding, (cap,), jnp.int32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cap", [FACT_CAP, DIM_CAP])
def test_filter_scan_compiles(one_chip, cap):
    block = min(2048, cap)
    fn = jax.jit(lambda cols, n: filter_scan(cols, LITERAL_PROGRAM, n,
                                             block=block))
    compiled = fn.lower(_columns(one_chip, cap),
                        _spec(one_chip, (), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("cap", [FACT_CAP, DIM_CAP])
@pytest.mark.parametrize("n_q", [1, 8])
def test_filter_scan_batch_compiles(one_chip, cap, n_q):
    block = min(2048, cap)
    fn = jax.jit(lambda cols, n, ic, fc: filter_scan_batch(
        cols, SLOTTED_PROGRAM, n, ic, fc, block=block))
    compiled = fn.lower(_columns(one_chip, cap),
                        _spec(one_chip, (), jnp.int32),
                        _spec(one_chip, (n_q, 1), jnp.int32),
                        _spec(one_chip, (n_q, 1), jnp.float32)).compile()
    _assert_kernel(compiled)


def test_parse_i32_compiles(one_chip):
    fn = jax.jit(lambda d: parse_i32(d))
    compiled = fn.lower(_spec(one_chip, (1 << 20, 10), jnp.uint8)).compile()
    _assert_kernel(compiled)


def test_fused_xla_mask_compact_compiles(one_chip):
    pred = E.and_(E.cmp("x", ">", 50.0), E.cmp("q", ">=", 10))
    fn = _fused_fn(None, pred, ("x", "q"), ("x", "q"), 1 << 22)
    fn.lower(_spec(one_chip, (), jnp.int32),
             *_columns(one_chip, FACT_CAP)).compile()


@pytest.mark.parametrize("carry", [False, True])
def test_selection_compiles(one_chip, carry):
    # the sort over a filter's mask (gathering the columns after it) or
    # a join's match counts (carrying them through it), then the
    # F2-sized output from it
    cols = _columns(one_chip, FACT_CAP)
    flags = _spec(one_chip, (FACT_CAP,), jnp.int32 if carry else jnp.bool_)
    keys, carried = jax.eval_shape(
        lambda f, *c: _select_rows(f, *c, carry=carry), flags, *cols)
    _select_rows.lower(flags, *cols, carry=carry).compile()
    shard = lambda a: _spec(one_chip, a.shape, a.dtype)  # noqa: E731
    _take_selected.lower(shard(keys), tuple(map(shard, carried)),
                         FACT_CAP // 2, *cols).compile()


def test_unique_join_gather_compiles(one_chip):
    # the fact side's matched rows into a 2^22 output: lo and two probe
    # columns carried through the sort, two build columns gathered from
    # the item dimension's 2,048-row capacity
    i32 = jnp.int32
    fact = _spec(one_chip, (FACT_CAP,), i32)
    _join_gather_unique.lower(
        fact, (fact,) + _columns(one_chip, FACT_CAP),
        _spec(one_chip, (2048,), i32), 1 << 22, 3,
        fact, *_columns(one_chip, FACT_CAP),
        *_columns(one_chip, 2048)).compile()


def test_sharded_scan_programs_compile(topo):
    # the four-chip path's programs at the SF10 fact capacity: per-shard
    # mask under shard_map, then the global compaction of 4 columns to
    # an F2-sized output (carried through the sort), all row-sharded
    # over the 2x2 mesh
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    pred = E.and_(E.cmp("x", ">", 50.0), E.cmp("q", ">=", 10))
    fn = _sharded_mask_fn(None, pred, ("x", "q"), (1, 1), mesh, "data")
    fn.lower(_spec(rep, (), jnp.int32), *_columns(row, FACT_CAP)).compile()
    _compact_nz.lower(_spec(row, (FACT_CAP,), jnp.bool_), FACT_CAP // 2,
                      *[_spec(row, (FACT_CAP,), jnp.float32)] * 4).compile()


def test_dense_join_probe_compiles(one_chip):
    from repro.relational.physical import _join_probe_dense

    i32 = jnp.int32
    _join_probe_dense.lower(
        _spec(one_chip, (FACT_CAP,), i32), _spec(one_chip, (2048,), i32),
        _spec(one_chip, (), i32), _spec(one_chip, (), i32),
        _spec(one_chip, (), i32), span=2048).compile()
