"""Device mesh + sharding for the document axis.

The reference's scale-out axis is per-document sharding (Kafka partitions by
documentId; each deli/lambda instance owns a disjoint doc set —
SURVEY.md §2.6).  The TPU-native equivalent is a 1-D ``Mesh`` over a ``docs``
axis: replica state arrays are sharded on their leading document dimension,
op batches likewise, and the per-step computation is purely doc-parallel so
the ``shard_map``-wrapped fleet programs below run with ZERO collectives on
the hot path (collectives appear only in aggregate metrics/reductions, e.g.
the per-shard error-latch reduce).

Layers:

- ``match_partition_rules``: regex partition-rule matching over a state
  pytree's named leaves -> a pytree of ``PartitionSpec`` (scalars and
  singleton leaves replicate; everything matching a doc rule shards on its
  leading document dimension).
- ``mesh_fleet_program``: wrap a per-doc fleet step (``apply_megastep`` /
  ``apply_nested_megastep`` / compaction) in ``shard_map`` under the mesh
  and ``jax.jit`` with the state donated — one dispatch steps every shard,
  each shard's obliterate gate evaluated from ITS OWN docs (a hot
  obliterate shard no longer de-specializes the whole fleet's trace).
- ``error_count``: the per-shard reduce replacing the full [D] error-vector
  gather on the recover() path — each shard contributes a partial sum, the
  host reads one scalar.

Multi-host pods extend the same mesh across hosts: the doc axis rides
ICI within a slice and DCN across slices — no code change, just a larger
``jax.devices()`` list.

The doc-axis shard index (device position along ``docs``) is also the
shard domain of the shared placement plane (``models/placement.py``):
``shard_of``/``free_slots``/``migrate_doc`` address THESE shards, so a
live migration is a slot handoff between two positions of the same
sharded state arrays — the mesh program never recompiles for a move,
and 2-D seg-lane docs keep their reserved doc-axis slot while promoted.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# The seg-axis NAME is owned by the kernel whose collectives bind to it
# (ops.mergetree_kernel's all_gather/psum/pmin inside apply_megastep_seg);
# re-exported here so mesh construction and the kernel can never disagree.
from ..ops.mergetree_kernel import SEG_AXIS


def doc_mesh(devices=None, axis: str = "docs") -> Mesh:
    """A 1-D mesh over all (or the given) devices for document parallelism."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs.reshape(-1), (axis,))


def docs_segs_mesh(
    devices=None, seg_shards: int = 1, doc_axis: str = "docs",
    seg_axis: str = SEG_AXIS,
) -> Mesh:
    """The 2-D docs x segs mesh: documents place over rows, a hot
    document's merge-tree segments block-shard over the ``segs`` columns.
    ``seg_shards`` clamps to the largest divisor of the device count at or
    below the request (the mesh must factor).  Cold docs still use every
    device — their fleet state shards over BOTH axes flattened
    (``fleet_doc_axes``); only hot docs carve the segs axis."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    n = devs.size
    seg = max(1, min(int(seg_shards), n))
    while n % seg:
        seg -= 1
    return Mesh(devs.reshape(n // seg, seg), (doc_axis, seg_axis))


def fleet_doc_axes(mesh: Mesh):
    """The PartitionSpec ENTRY for a fleet state's leading doc dimension on
    this mesh: the plain docs axis on a 1-D mesh, both axes flattened on a
    docs x segs mesh (cold docs keep using every device)."""
    if SEG_AXIS in mesh.axis_names:
        return ("docs", SEG_AXIS)
    return "docs"


def shard_docs(mesh: Mesh, axis=None) -> NamedSharding:
    """Sharding for arrays with a leading document dimension."""
    return NamedSharding(
        mesh, P(axis if axis is not None else fleet_doc_axes(mesh))
    )


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Partition-rule matching over named pytree leaves
# ---------------------------------------------------------------------------

def _key_str(k) -> str:
    """One path entry -> its name (GetAttrKey/SequenceKey/DictKey/...)."""
    for attr in ("name", "idx", "key"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def named_leaves(tree) -> tuple[list[str], list, object]:
    """``(names, leaves, treedef)`` with "a/b/0"-style leaf path names."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(_key_str(k) for k in path) for path, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


def match_partition_rules(rules, tree, default: P = P()):
    """A pytree of ``PartitionSpec`` matching ``tree``: first rule whose
    regex matches the leaf's path name wins; 0-d and singleton leaves
    always replicate (never partition scalars); unmatched leaves take
    ``default`` (replicated)."""
    names, leaves, treedef = named_leaves(tree)
    specs = []
    for name, leaf in zip(names, leaves):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            specs.append(P())
            continue
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                specs.append(spec)
                break
        else:
            specs.append(default)
    return jax.tree_util.tree_unflatten(treedef, specs)


# The batched engines broadcast every replica leaf to [D, ...], so every
# named leaf of a fleet state carries the leading document axis — per-doc
# scalars included (they are [D] vectors in the batch).  Anything that ever
# loses the doc axis (a future shared pool / global table) falls through to
# the replicated default via the scalar/singleton guard or a non-match.
FLEET_STATE_RULES: tuple = ((r".*", P("docs")),)


def fleet_state_specs(state, doc_axes="docs"):
    """Partition specs for a batched engine state pytree (leading doc dim
    sharded over ``doc_axes`` — the plain docs axis, or both axes of a
    docs x segs mesh via ``fleet_doc_axes`` — scalars/singletons
    replicated)."""
    rules = FLEET_STATE_RULES if doc_axes == "docs" else ((r".*", P(doc_axes)),)
    return match_partition_rules(rules, state)


def shard_fleet_state(state, mesh: Mesh):
    """Place a batched fleet state on the mesh per its matched specs."""
    specs = fleet_state_specs(state, fleet_doc_axes(mesh))
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs
    )


def init_fleet_state(proto, capacity: int, mesh: Mesh | None = None):
    """A ``[capacity, ...]`` fleet of pristine ``proto`` rows.  On a mesh it
    is built in place: one jitted broadcast with sharded outputs, so every
    device makes its own rows and none ever holds the whole fleet.
    Broadcasting first and ``shard_fleet_state`` after put all of it on the
    default device before it was split: 6.2 GB of a 10,000-document string
    fleet on device 0 of four, whose peak then read 8.86 GB against 1.70 GB
    on the others (PERF.md, PR 32)."""

    def fleet(p):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (capacity,) + x.shape), p
        )

    if mesh is None:
        return fleet(proto)
    shapes = jax.eval_shape(fleet, proto)
    specs = fleet_state_specs(shapes, fleet_doc_axes(mesh))
    shardings = jax.tree.map(
        lambda _, s: NamedSharding(mesh, s), shapes, specs
    )
    return jax.jit(fleet, out_shardings=shardings)(proto)


# ---------------------------------------------------------------------------
# Segment-axis partition rules (hot docs on the docs x segs mesh)
# ---------------------------------------------------------------------------

def seg_state_specs(state, axis: str = SEG_AXIS):
    """Partition specs for a SEG-SHARDED single-doc ``DocState``
    (ops.mergetree_kernel.seg_shard_state layout): per-segment arrays and
    the per-shard live-count vector block-shard over ``axis``; the text
    pool, scalars, and the obliterate window table replicate — the
    ``_specs_for`` layout of parallel/long_doc.py promoted to the serving
    path (where ``nseg`` must be per-shard because inserts land
    shard-local)."""
    from ..ops.mergetree_kernel import DocState

    s, r = P(axis), P()
    return DocState(
        text=r, text_end=r, nseg=s,
        seg_start=s, seg_len=s, ins_key=s, ins_client=s,
        seg_uid=s, seg_obpre=s,
        rem_keys=(s,) * len(state.rem_keys),
        rem_clients=(s,) * len(state.rem_clients),
        prop_keys=(s,) * len(state.prop_keys),
        prop_vals=(s,) * len(state.prop_vals),
        uid_next=r, ob_key=r, ob_client=r, ob_start_uid=r, ob_end_uid=r,
        ob_start_side=r, ob_end_side=r, ob_ref_seq=r,
        min_seq=r, error=r,
    )


def shard_seg_state(state, mesh: Mesh, axis: str = SEG_AXIS):
    """Place a seg-sharded single-doc state on the mesh per its specs."""
    specs = seg_state_specs(state, axis)
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), state, specs
    )


@functools.lru_cache(maxsize=None)
def mesh_seg_program(step_fn, mesh: Mesh, state_specs,
                     arg_specs: tuple = (P(), P()), donate: bool = False):
    """``jit(shard_map(step_fn))`` over the SEGMENT axis: one dispatch
    applies a [K, B] op ring to one seg-sharded hot document, the
    per-segment work split across the segs shards with the two collective
    hops inside (ops.mergetree_kernel.apply_megastep_seg).  Cached per
    (fn, mesh, specs) like ``mesh_fleet_program`` so every segment lane
    serving the same mesh shares one compile.

    ``donate`` defaults OFF, deliberately: with donation, an executable
    for this program RELOADED from the persistent XLA compile cache
    returned permuted/garbage output buffers whenever the obliterate
    branch executed.  That was seen on jax 0.4.37, CPU backend (freshly
    compiled executables were always correct, and
    tests/test_segment_parallel.py guards the byte-identity contract that
    caught it).  On the installed jax 0.9.0 a two-process retry (three
    seeds, donated, reloaded twice from the cache, CPU) stayed
    byte-identical, which is a non-reproduction, not a proof: turning
    donation on is its own change, with this program in the on-chip
    smoke's warm leg first."""
    mapped = jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(state_specs,) + tuple(arg_specs),
        out_specs=state_specs,
        check_vma=False,  # replicated leaves are replicated by construction
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# shard_map-wrapped fleet programs
# ---------------------------------------------------------------------------

def op_spec(ndim: int, axis: str = "docs") -> P:
    """Spec for an op/payload tensor whose doc axis sits at ``ndim - 3``
    ([..., D, B, F|L]): megastep rings [K, D, B, *] -> P(None, docs),
    single slices [D, B, *] -> P(docs)."""
    return P(*([None] * (ndim - 3)), axis)


@functools.lru_cache(maxsize=None)
def mesh_fleet_program(step_fn, mesh: Mesh, state_specs,
                       arg_specs: tuple = (P(None, "docs"), P(None, "docs")),
                       donate: bool = True):
    """``jit(shard_map(step_fn))``: ONE donated dispatch steps the whole
    fleet, each shard applying its own doc rows with no cross-shard
    communication.  ``state_specs`` must be the hashable pytree
    ``fleet_state_specs`` produces for the engine's state type (NamedTuple
    of PartitionSpec) and ``arg_specs`` the specs of the non-state args
    (default: a [K, D, B, *] megastep op ring pair), so the program cache
    is shared by every engine instance serving the same mesh."""
    mapped = jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(state_specs,) + tuple(arg_specs),
        out_specs=state_specs,
        check_vma=False,  # per-doc program: nothing is replicated to check
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


@jax.jit
def error_count(error: jnp.ndarray) -> jnp.ndarray:
    """Fleet error-latch probe as a per-shard reduce: each shard partial-
    sums its own error rows and the host reads ONE scalar — the recover()
    gate no longer gathers the full [D] error vector across the mesh every
    step (the gather happens only when this count is nonzero)."""
    return jnp.sum((error != 0).astype(jnp.int32))

