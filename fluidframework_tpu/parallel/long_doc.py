"""Segment-axis sharding: one huge document spread across the mesh.

The reference's long-sequence machinery — the merge-tree B-tree with
``PartialSequenceLengths`` giving O(log n) position resolution
(merge-tree/src/partialLengths.ts:230, SURVEY §5 "long-context") — exists
only to make prefix-length queries cheap on one CPU. The TPU-native form
(SURVEY §7): the flat segment SoA is block-sharded over a ``segs`` mesh
axis (order-preserving), per-shard partial lengths are combined with ICI
collectives, and every position query becomes

    global prefix  =  all_gather of shard totals (one tiny collective)
    local resolve  =  masked prefix-sum inside the shard (vector ops)
    combine        =  psum of per-shard one-hot results

— the distributed analog of the B-tree walk: two collective hops regardless
of document size. Range ops (remove/annotate) then apply as purely-local
mask updates. This composes with the ``docs`` axis as a 2-D mesh
(docs × segs): fleets of huge documents — documents across chips, segments
across chips — sequence parallelism for collaborative text.

Inserts migrate between shards only at rebalance points (the zamboni
compaction pass already gathers live segments; a sharded rebalance
re-blocks them), so the hot query path stays at the two hops above.

PROMOTED (PR 11): the serving engines now run this design end to end —
``ops.mergetree_kernel.apply_megastep_seg`` is the segment-parallel apply
(full op semantics, byte-identical to the single-lane kernel),
``parallel.mesh.seg_state_specs``/``docs_segs_mesh`` carry the layout and
the 2-D mesh, and ``DocBatchEngine`` segment lanes serve hot docs with it.
This module remains the read-side query plane (visible_length / resolve /
mark over an equal-block layout with replicated nseg) and the design
reference for the two-hop scheme.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.flight_recorder import span
from ..ops.mergetree_kernel import DocState
from ..protocol.stamps import NO_REMOVE

I32 = jnp.int32


def shard_doc_state(state: DocState, mesh: Mesh, axis: str = "segs") -> DocState:
    """Place a single-doc state with segment arrays block-sharded over
    ``axis`` and scalars/text replicated. Block sharding preserves segment
    order: shard k owns the k-th contiguous run."""
    seg = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    specs = _specs_for(state, axis)
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, seg if sp == P(axis) else rep),
        state,
        specs,
    )


def _specs_for(state: DocState, axis: str) -> DocState:
    s, r = P(axis), P()
    return DocState(
        text=r, text_end=r, nseg=r,
        seg_start=s, seg_len=s, ins_key=s, ins_client=s,
        seg_uid=s, seg_obpre=s,
        rem_keys=(s,) * len(state.rem_keys),
        rem_clients=(s,) * len(state.rem_clients),
        prop_keys=(s,) * len(state.prop_keys),
        prop_vals=(s,) * len(state.prop_vals),
        # The obliterate window table is tiny: replicate it like scalars.
        uid_next=r, ob_key=r, ob_client=r, ob_start_uid=r, ob_end_uid=r,
        ob_start_side=r, ob_end_side=r, ob_ref_seq=r,
        min_seq=r, error=r,
    )


def _local_vis_lens(s: DocState, ref_seq, client, axis: str) -> jnp.ndarray:
    """Per-shard perspective-visible lengths, with GLOBAL aliveness (local
    row k is global row my_shard * S_local + k against the replicated
    nseg)."""
    my = jax.lax.axis_index(axis)
    n_local = s.seg_len.shape[0]
    gidx = my * n_local + jnp.arange(n_local, dtype=I32)
    alive = gidx < s.nseg
    ins_occ = (s.ins_key <= ref_seq) | (s.ins_client == client)
    rem_occ = jnp.zeros_like(alive)
    for k, c in zip(s.rem_keys, s.rem_clients):
        rem_occ = rem_occ | (k <= ref_seq) | (c == client)
    vis = alive & ins_occ & ~rem_occ
    return jnp.where(vis, s.seg_len, 0)


def _shard_offset(lens: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Sum of EARLIER shards' visible totals (one all_gather): the offset
    translating this shard's local coordinates to global ones."""
    totals = jax.lax.all_gather(jnp.sum(lens), axis)  # [n_shards]
    my = jax.lax.axis_index(axis)
    return jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < my, totals, 0))


def _global_prefix(lens: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Per-segment exclusive prefix in GLOBAL visible coordinates: local
    cumsum shifted by the earlier shards' totals."""
    return jnp.cumsum(lens) - lens + _shard_offset(lens, axis)


def make_sharded_ops(mesh: Mesh, state: DocState, axis: str = "segs"):
    """Build (visible_length, resolve_positions, mark_range) for one
    document layout, each shard_map-jitted over the segment axis."""
    specs = _specs_for(state, axis)

    # check_vma=False throughout: every output is a psum (replicated by
    # construction) or per-shard state, and the TPU form of _resolve calls
    # a Pallas kernel, which carries no varying-axes annotation.
    @partial(
        jax.shard_map, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P(),
        check_vma=False,
    )
    def _visible_length(s: DocState, ref_seq, client):
        return jax.lax.psum(jnp.sum(_local_vis_lens(s, ref_seq, client, axis)), axis)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, P(), P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )
    def _resolve(s: DocState, positions, ref_seq, client):
        """positions[Q] (replicated, in perspective-visible coordinates) ->
        (global segment index, offset within segment) per query.

        The shard-local membership search runs as the blocked Pallas
        kernel on TPU (ops/pallas_kernels.py — streams the segment axis
        through VMEM instead of materializing [Q, S_local] in HBM); shard
        coordinates reduce to local ones by subtracting the earlier
        shards' visible total, then one psum merges the per-shard
        one-hots."""
        from ..ops.pallas_kernels import resolve_positions_blocked

        lens = _local_vis_lens(s, ref_seq, client, axis)
        my = jax.lax.axis_index(axis)
        local_q = positions - _shard_offset(lens, axis)
        local_idx, offset, hit = resolve_positions_blocked(lens, local_q)
        n_local = lens.shape[0]
        global_idx = jnp.where(hit == 1, my * n_local + local_idx, 0)
        # Exactly one shard hits each in-range query; psum merges one-hots.
        return (
            jax.lax.psum(global_idx.astype(I32), axis),
            jax.lax.psum(jnp.where(hit == 1, offset, 0).astype(I32), axis),
        )

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(specs, P(), P(), P(), P(), P(), P()),
        out_specs=specs,
        check_vma=False,
    )
    def _mark_range(s: DocState, p1, p2, op_key, op_client, ref_seq, client):
        """Remove [p1, p2) under the op's perspective as a purely-local mask
        update (whole segments in range; boundary splits are the single-
        owner engine's job before a doc graduates to sharded layout — large
        deletes over long documents mark thousands of whole segments)."""
        lens = _local_vis_lens(s, ref_seq, client, axis)
        prefix = _global_prefix(lens, axis)
        vis = lens > 0
        in_range = vis & (prefix >= p1) & ((prefix + lens) <= p2)
        new_rem_keys = []
        new_rem_clients = []
        taken = jnp.zeros_like(in_range)
        for rk, rc in zip(s.rem_keys, s.rem_clients):
            free = (rk == NO_REMOVE) & in_range & ~taken
            new_rem_keys.append(jnp.where(free, op_key, rk).astype(I32))
            new_rem_clients.append(jnp.where(free, op_client, rc).astype(I32))
            taken = taken | free
        return s._replace(
            rem_keys=tuple(new_rem_keys), rem_clients=tuple(new_rem_clients)
        )

    n_shards = int(mesh.shape[axis])
    # jit the shard_map programs and span AROUND the jitted call: a span
    # inside the traced body fires once at trace time and never again
    # (the compiled executable dispatches without re-entering Python), so
    # it would record compile cost, not per-dispatch collective hops.
    jit_visible = jax.jit(_visible_length)
    jit_resolve = jax.jit(_resolve)
    jit_mark = jax.jit(_mark_range)

    def visible_length(s, ref_seq, client):
        # One trace span per collective program dispatch: the hop-1
        # all-gather + hop-2 psum pair lives inside the jitted program,
        # so the span is the host-visible record of the two-hop cost.
        with span("seg_collective", op="visible_length", shards=n_shards):
            return jit_visible(
                s, jnp.asarray(ref_seq, I32), jnp.asarray(client, I32)
            )

    def resolve_positions(s, positions, ref_seq, client):
        with span("seg_collective", op="resolve", shards=n_shards):
            return jit_resolve(
                s, jnp.asarray(positions, I32),
                jnp.asarray(ref_seq, I32), jnp.asarray(client, I32),
            )

    def mark_range(s, p1, p2, op_key, op_client, ref_seq, client):
        with span("seg_collective", op="mark_range", shards=n_shards):
            return jit_mark(
                s, jnp.asarray(p1, I32), jnp.asarray(p2, I32),
                jnp.asarray(op_key, I32), jnp.asarray(op_client, I32),
                jnp.asarray(ref_seq, I32), jnp.asarray(client, I32),
            )

    return (visible_length, resolve_positions, mark_range)
