"""The main-path kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a *described* v5e
(``jax.experimental.topologies``) and raises what the chip's compiler
would raise — unaligned blocks, unsupported Mosaic lowerings, programs
that do not fit.  Nothing runs, so this says nothing about results or
times; the kernels' answers are checked in interpret mode by
``test_kernels.py``.

Widths are those of TPC-H SF10's partsupp (8M rows → 2^23) against
part (2M → 2^21).  The topology is described inside a module fixture —
never at import, in ``skipif`` or in ``parametrize`` — because only one
process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.autotune import DEFAULT_CONFIG
from repro.kernels.freq_join import freq_join_pallas
from repro.kernels.segment_sum import segment_sum_pallas
from repro.kernels.semi_join import semi_join_pallas

PARENT = 1 << 23
CHILD = 1 << 21


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _shape(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _join_args(sharding, fdt=jnp.int32):
    return (_shape(PARENT, jnp.int32, sharding), _shape(PARENT, fdt, sharding),
            _shape(CHILD, jnp.int32, sharding), _shape(CHILD, fdt, sharding))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("mode", ["sum", "any"])
@pytest.mark.parametrize("domain", [None, CHILD], ids=["sort", "dense"])
def test_xla_freq_join_compiles(one_chip, mode, domain):
    fn = functools.partial(ops._freq_join_impl, mode=mode, backend="xla",
                           interpret=False, domain=domain,
                           config=DEFAULT_CONFIG)
    assert DEFAULT_CONFIG.dense_ok(domain, CHILD) == (domain is not None)
    _compile(fn, *_join_args(one_chip))


def test_xla_group_by_sum_compiles(one_chip):
    fn = functools.partial(ops.group_by_sum, backend="xla")
    _compile(fn, _shape(PARENT, jnp.int32, one_chip),
             _shape(PARENT, jnp.int32, one_chip))


def test_weighted_percentile_compiles(one_chip):
    _compile(lambda v, w: ops.weighted_percentile(v, w, 0.5),
             _shape(PARENT, jnp.float32, one_chip),
             _shape(PARENT, jnp.int32, one_chip))


@pytest.mark.parametrize("block_rows", [8, 64])
@pytest.mark.parametrize("kernel", ["freq_join", "semi_join"])
def test_pallas_join_compiles(one_chip, kernel, block_rows):
    fn = freq_join_pallas if kernel == "freq_join" else semi_join_pallas
    fn = functools.partial(fn, interpret=False,
                           parent_block_rows=block_rows,
                           child_block_rows=block_rows)
    compiled = _compile(fn, *_join_args(one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes_wide", [1024, 8192])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32],
                         ids=["int32", "float32"])
def test_pallas_segment_sum_compiles(one_chip, dtype, lanes_wide):
    fn = functools.partial(segment_sum_pallas, interpret=False,
                           lanes_wide=lanes_wide)
    compiled = _compile(fn, _shape(PARENT, jnp.int32, one_chip),
                        _shape(PARENT, dtype, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_freq_join_with_64_bit_counts_compiles(one_chip):
    """The dense freq-join with s64 counts compiles, scatters and gathers
    32-bit limbs only, and keeps the kernel scopes the device trace's
    reduction reads."""
    from repro.core.scopes import scope_table
    from repro.core.stats import limb_split

    # the graph cell's split: a hub of 160,410 rows, frequencies to 2^40
    fn = functools.partial(ops.freq_join, mode="sum", backend="xla",
                           domain=CHILD, config=DEFAULT_CONFIG,
                           limbs=limb_split(160_410, 2 ** 40))
    with jax.enable_x64(True):
        compiled = _compile(fn, *_join_args(one_chip, jnp.int64))
    text = compiled.as_text()
    assert "s64[" in text
    # the scatter-adds write 32-bit limbs, never s64
    assert not re.findall(r"^.*= s64\[[^\n]*\bscatter\(", text, re.M)
    paths = set(scope_table(text).values())
    assert {"freq_join/scatter", "freq_join/gather"} <= paths


def test_fused_walks_keep_their_scatter_sorts_in_the_freq_join(one_chip):
    """walk2 fused with walk3 rooted at its middle atom, at the widths of
    Graph500 SCALE 22 (2^26 edges over 2^22 vertices): the freq-joins
    call the kernel with one signature, yet every instruction keeps the
    operator and kernel scopes of its call site.
    The index sorts the TPU compiler emits before each colliding
    scatter-add read as ``freq_join/freq_join/scatter``, so the device
    trace counts them as freq-join time, and the shared join still
    scatters once."""
    import numpy as np

    from repro.core import Executor, parse_sql, plan_query
    from repro.core.rewrite import rerooted
    from repro.core.scopes import scope_table
    from repro.core.stats import StatsCatalog, count_plan, widest
    from repro.tables.table import ColumnMeta, RelSchema, Schema, Table

    VERTICES, EDGES = 1 << 22, 1 << 26
    schema = Schema(relations={"edge": RelSchema("edge", (
        ColumnMeta("src", domain=VERTICES),
        ColumnMeta("dst", domain=VERTICES)))}, foreign_keys=())
    # statistics from a hub of 2^16 self-loops: walk3's counts need 64 bits
    rng = np.random.default_rng(17)
    cols = [np.concatenate([np.zeros(1 << 16, np.int32),
                            rng.integers(0, VERTICES, 4096, dtype=np.int32)])
            for _ in range(2)]
    db = {"edge": Table.from_numpy(dict(zip(("src", "dst"), cols)))}
    cat = StatsCatalog(schema)
    cat.refresh("edge", db["edge"], db)
    plans = [plan_query(parse_sql(sql, schema), schema, mode="opt_plus",
                        stats=cat) for sql in (
        "SELECT COUNT(*) FROM edge e0, edge e1 WHERE e0.dst = e1.src",
        "SELECT COUNT(*) FROM edge e0, edge e1, edge e2 "
        "WHERE e0.dst = e1.src AND e1.dst = e2.src")]
    assert [rerooted(p) for p in plans] == [False, True]
    counts = widest([count_plan(p, cat.tables()) for p in plans])
    fn = Executor(db, schema).compile_multi(plans, counts=counts)
    spec = {"edge": jax.tree.map(
        lambda a: _shape(EDGES, a.dtype, one_chip), db["edge"])}
    with jax.enable_x64(True):
        text = fn.lower(spec).compile().as_text()
    table = scope_table(text)
    names = {op: re.findall(
        rf"^\s+(?:ROOT )?%([^\s=]+) = [^\n]*\b{op}\(", text, re.M)
        for op in ("sort", "scatter")}
    assert len(names["scatter"]) == 2 and len(names["sort"]) == 2
    assert {table.get(n) for n in names["sort"]} \
        == {"freq_join/freq_join/scatter"}
    # no instruction falls outside the operator that runs it
    operators = {"scan", "freq_join", "final_agg"}
    assert {p.split("/")[0] for p in table.values()} <= operators
