"""The text pool's strip write, compiled HERE for a TPU v5e that is described
and not attached (the TPU's compiler is installed in the sandbox): Mosaic
accepts the kernels at the fleets' real widths, the pool goes in and out in
place, and no pool-sized temporary is made; the cohort trio's gather and
scatter never see the pool, and its step takes it aliased.  Nothing runs, so
nothing here is a time; interpret mode (tests/test_pallas_kernels.py) holds
the results.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fluidframework_tpu.ops import pallas_kernels as pk

I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable for a described device can be written to the persistent
    # cache but not read back: keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n_docs,capacity,window", [
    (6144, 65536, 32 * 8),      # string_fleet_1chip: fleet_main's geometry
    (2500, 65536, 32 * 8),      # a shard of string_fleet_10k_mesh4: 312 tile rows + 4
    (64, 65536, 32 * 8),        # a cohort
    (1024, 16384, 32 * 64),     # the engine's default max_insert_len
    (64, 1024, 32 * 64),        # a pool narrower than the window: whole rows
    (16, 200, 64),              # a pool not made of lanes: whole rows too
], ids=["fleet_6144", "mesh_shard_2500", "cohort_64", "insert_len_64",
        "whole_row", "row_not_of_lanes"])
def test_strip_write_compiles_for_the_v5e(one_chip, monkeypatch, n_docs,
                                          capacity, window):
    # ``write_text_strips`` interprets the kernel unless the backend is a
    # TPU; the backend here is the CPU, the target is not.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    width = pk.text_strip_width(capacity, window)
    assert width == min(-(-window // pk.LANES) * pk.LANES + pk.LANES, capacity)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    compiled = jax.jit(pk.write_text_strips, donate_argnums=0).lower(
        arg(n_docs, capacity), arg(n_docs), arg(n_docs, width),
        arg(n_docs, width)).compile()
    text = compiled.as_text()
    # (A pool that is not made of whole lanes takes plain updates alone.)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        capacity % pk.LANES == 0)
    # In place: the pool's bytes are aliased to the result, and what the
    # program keeps beside its arguments is strips, not a pool.
    memory = compiled.memory_analysis()
    # (On the device a pool is whole tiles: 2,500 rows are 2,504.)
    pool_bytes = (-(-n_docs // pk.SUBLANES) * pk.SUBLANES
                  * -(-capacity // pk.LANES) * pk.LANES * 4)
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 8
    relaid = re.compile(r"= s32\[%d\]" % (n_docs * capacity))
    assert not relaid.search(text)
    for op in ("scatter(", "copy(", "reshape("):
        assert not [ln for ln in text.splitlines() if op in ln
                    and f"s32[{n_docs},{capacity}]" in ln.split(op)[0]], op


@pytest.mark.parametrize("lanes", [1, 4])
def test_cohort_compaction_moves_rows_and_never_the_fleet(one_chip, lanes):
    """``_compact_cohort`` at fleet_main's geometry (6,144 x 4,096): the
    donated columns come back aliased, the only instructions whose result
    is a whole [6144, 4096] column are its in-place row updates, what the
    program keeps beside its arguments is a few rows, and the text pool is
    no operand of it (PR 35)."""
    from fluidframework_tpu.models import doc_batch_engine as dbe
    from fluidframework_tpu.ops import mergetree_kernel as mk

    n_docs, segments = 6144, 4096
    proto = jax.eval_shape(lambda: mk.init_state(segments, 4, 4, 65536, 8))
    cols = {
        f: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (n_docs, *x.shape), x.dtype, sharding=one_chip),
            getattr(proto, f))
        for f in dbe._COMPACT_FIELDS
    }
    assert "text" not in cols and "text_end" not in cols
    idx = jax.ShapeDtypeStruct((lanes,), I32, sharding=one_chip)
    compiled = dbe._compact_cohort.lower(cols, idx, idx).compile()
    memory = compiled.memory_analysis()
    column = n_docs * segments * 4
    assert memory.alias_size_in_bytes >= 22 * column
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 4096
    assert memory.temp_size_in_bytes < column // 16
    text = compiled.as_text()
    assert "65536" not in text            # no pool, of the fleet or of a row
    whole = re.compile(
        r"= \(?s32\[%d,%d\]\S* ([\w-]+)\(" % (n_docs, segments))
    ops = {m.group(1) for m in whole.finditer(text)}
    assert ops <= {"parameter", "dynamic-update-slice", "get-tuple-element",
                   "tuple", "bitcast", "while"}, ops


@pytest.mark.parametrize("n_docs,capacity,window,lanes", [
    (6144, 65536, 32 * 8, 1),
    (6144, 65536, 32 * 8, 64),
    (6144, 65536, 32 * 8, 1024),    # four grid steps of STRIP_ROW_LANES
    (1024, 16384, 32 * 64, 256),    # the engine's default max_insert_len
    (64, 1024, 32 * 64, 16),        # a pool narrower than the window
], ids=["one_lane", "cohort_64", "cohort_1024", "insert_len_64", "whole_row"])
def test_strip_write_at_rows_compiles_for_the_v5e(one_chip, monkeypatch,
                                                  n_docs, capacity, window,
                                                  lanes):
    """``write_text_strips_at`` at the cohort sizes of fleet_main's geometry:
    Mosaic takes it, the pool's bytes are aliased to the result, and beside
    its arguments the program keeps a few strips at most, never a pool."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    width = pk.text_strip_width(capacity, window)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    compiled = jax.jit(pk.write_text_strips_at, donate_argnums=0).lower(
        arg(n_docs, capacity), arg(lanes), arg(lanes), arg(lanes, width),
        arg(lanes, width)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == n_docs * capacity * 4
    assert memory.temp_size_in_bytes <= (
        (1 << 18) + 4 * lanes * pk.SUBLANES * width * 4)
    for op in ("scatter(", "copy(", "reshape(", "gather("):
        assert not [ln for ln in text.splitlines() if op in ln
                    and f"s32[{n_docs},{capacity}]" in ln.split(op)[0]], op


def test_cohort_trio_moves_rows_and_never_the_pool(one_chip, monkeypatch):
    """The cohort trio at fleet_main's geometry (6,144 x 4,096 x 65,536), 64
    lanes: the gather's and the scatter's programs have no operand, result
    or temporary with the pool's 65,536 columns (or half of them: XLA split
    the pool in two to gather 64 rows of it, a copy of 1.6 GB a trio until
    PR 37); the step takes the pool aliased in and out, the only
    instructions whose result is a whole pool hand it on in place, and what
    it keeps beside its arguments is rows."""
    from fluidframework_tpu.models import doc_batch_engine as dbe
    from fluidframework_tpu.ops import mergetree_kernel as mk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_docs, lanes, chars = 6144, 64, 65536
    proto = jax.eval_shape(lambda: mk.init_state(4096, 4, 4, chars, 8))

    def rows(n):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (n, *x.shape), x.dtype, sharding=one_chip), proto)

    def arg(*shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fleet = rows(n_docs)
    rest = fleet._replace(text=None)
    sub = rows(lanes)._replace(text=arg(lanes, 0))
    pool_bytes = n_docs * chars * 4
    wide = re.compile(r"s32\[\d+,(?:65536|32768)\]")

    gather = dbe._gather_cohort_jit.lower(rest, arg(lanes)).compile()
    assert not wide.search(gather.as_text())
    assert jax.tree.structure(gather.out_info) == jax.tree.structure(sub)
    assert gather.memory_analysis().temp_size_in_bytes < pool_bytes // 64

    scatter = dbe._scatter_cohort_jit.lower(
        rest, sub, arg(lanes), arg(lanes, dtype=jnp.bool_)).compile()
    assert not wide.search(scatter.as_text())
    memory = scatter.memory_analysis()
    assert memory.temp_size_in_bytes < pool_bytes // 64
    assert memory.alias_size_in_bytes > 22 * n_docs * 4096 * 4

    step = dbe._cohort_fleet_step.lower(
        fleet.text, sub, arg(lanes), arg(lanes, 32, mk.OP_FIELDS),
        arg(lanes, 32, 8)).compile()
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 4096
    assert memory.temp_size_in_bytes < pool_bytes // 64
    text = step.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    whole = re.compile(
        r"= \(?s32\[%d,(?:65536|32768)\]\S* ([\w-]+)\(" % n_docs)
    ops = {m.group(1) for m in whole.finditer(text)}
    assert ops <= {"parameter", "custom-call", "get-tuple-element", "tuple",
                   "bitcast", "while"}, ops


def test_fleet_step_has_no_pass_of_its_own_for_the_inserts_slot(
        one_chip, monkeypatch):
    """``_fleet_step`` at fleet_main's geometry (6,144 x 4,096 x 65,536,
    B = 32): the insert's segment rides the second slot of the boundary
    cuts' rewrite, so no instruction under ``insert/open_slot`` makes a
    whole [6144, 4096] column (five fusions and 6.65 of a row's 29.56 GB
    until PR 45), the cuts' rewrite is still there under its own scope, and
    what the program keeps beside its arguments stays under the 2.27 GiB it
    kept with that pass."""
    from fluidframework_tpu.models import doc_batch_engine as dbe
    from fluidframework_tpu.ops import mergetree_kernel as mk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n_docs, segments = 6144, 4096
    proto = jax.eval_shape(lambda: mk.init_state(segments, 4, 4, 65536, 8))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (n_docs, *x.shape), x.dtype, sharding=one_chip), proto)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    compiled = dbe._fleet_step.lower(
        state, arg(n_docs, 32, mk.OP_FIELDS), arg(n_docs, 32, 8)).compile()
    column = re.compile(r" = \(?[^=]*s32\[%d,%d\]" % (n_docs, segments))
    scoped = [ln for ln in compiled.as_text().splitlines()
              if "/open_slot" in ln and column.search(ln.split(" fusion(")[0])]
    assert scoped and all(
        "shared/ensure_boundary/open_slot" in ln for ln in scoped)
    assert not [ln for ln in scoped if "insert/open_slot" in ln]
    assert compiled.memory_analysis().temp_size_in_bytes < int(2.27 * 2**30)


def test_matrix_step_keeps_the_cell_planes_in_place(one_chip, monkeypatch):
    """The matrix fleet's one program at matrix_fleet_2048x256's geometry:
    the four [D, HR, HC] planes go through the row loop aliased, touched by
    the cell-write kernel alone (XLA's own scatter of D single words relaid
    each plane to one axis and back, 0.8 GB a plane a row)."""
    from fluidframework_tpu.models import matrix_batch_engine as mbe
    from fluidframework_tpu.ops import matrix_kernel as mxk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, rows, cols, segs, batch = 2048, 320, 384, 256, 32
    proto = jax.eval_shape(lambda: mxk.init_state(rows, cols, segs))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype,
                                       sharding=one_chip), proto)
    ops = jax.ShapeDtypeStruct((n, batch, mxk.MATRIX_OP_FIELDS), I32,
                               sharding=one_chip)
    compiled = mbe._matrix_step.lower(state, ops).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    plane = "s32[%d,%d,%d]" % (n, rows, cols)
    produced = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?\)?) ([\w\-]+)\(",
                     line)
        if m and plane in m.group(1):
            produced.add(m.group(2))
    # Parameters in, the kernel's aliased results, and tuple plumbing: no
    # copy, select, scatter, transpose or reshape makes a plane.
    assert produced <= {"parameter", "get-tuple-element", "tuple", "while",
                        "custom-call"}, produced
    assert not re.search(r"= s32\[%d\]" % (n * rows * cols), text)
    memory = compiled.memory_analysis()
    planes_bytes = 4 * n * rows * cols * 4
    assert memory.alias_size_in_bytes >= planes_bytes
    assert memory.temp_size_in_bytes < planes_bytes // 32
