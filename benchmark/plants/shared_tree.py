"""The SharedTree family: the TCP front and sequencer (``NetworkServer``),
in-process ``SharedTreeChannel`` writers, the fill that builds each document's
tree, one steady edit, and the comparison that decides ``correct``.

``TreeWriter`` and ``tree_edit`` are copies of ``chip_smoke.py``'s
``TreeWriter`` / ``_tree_edit`` (PR 21, sound on the chip); ``Plant`` follows
``plants/shared_string.py``.  Differences from the originals: a nack is
counted instead of raised, the four kinds of edit carry weights from the
configuration, and the edits of one flush never address a root node that
another edit of the same flush removes (see ``tree_edit``), and a
document's writers but the first open it once its fill is sequenced.

What ``correct`` rests on, outside this file: the plain reference is
``fluidframework_tpu.loadgen.coordinator.oracle_tree`` (an object-mark
``EditManager()`` and a ``Forest`` from ``dds/tree/editmanager.py``,
``dds/tree/forest.py`` and ``dds/tree/changeset.py`` replaying the sequencer's
log: no mark pool, no translation plan, no kernel), and every writer is a
``dds/tree/shared_tree.SharedTreeChannel`` replica.  Both are the package's
host-side Python, independent of the fleet's pooled fold and of the device
kernel, not of the repo.

Nothing here imports JAX.
"""

from __future__ import annotations

import gc
import json
import os
import random
import time

from child import BenchFailure

from fluidframework_tpu.dds.tree.changeset import (
    make_insert,
    make_remove,
    make_set_value,
)
from fluidframework_tpu.dds.tree.schema import leaf
from fluidframework_tpu.dds.tree.shared_tree import SharedTreeChannel
from fluidframework_tpu.protocol.channel import (
    ChannelDeltaConnection,
    ChannelMessage,
    MessageCollection,
    MessageEnvelope,
)
from fluidframework_tpu.protocol.messages import (
    MessageType,
    Nack,
    UnsequencedMessage,
)

SUB_FIELD = "sub"


def require_servable_step() -> None:
    """Fail at once, before any child starts, at a commit whose nested
    forest step cannot serve this family's geometry.  Until PR 28 the step
    looked up every row's parent with element-wise gathers for every
    document and op slot: 20.2 s a step at 256 documents x 16,384 slots on
    the v5e (PERF.md section 6), so a run there fills for ten minutes and
    then never reaches its ``done`` line.  The slot-wise step is
    ``ops/tree_kernel.apply_nested_fleet``; the file is read, not imported
    (it imports JAX, and this process must not)."""
    import fluidframework_tpu

    path = os.path.join(os.path.dirname(fluidframework_tpu.__file__),
                        "ops", "tree_kernel.py")
    with open(path) as f:
        if "def apply_nested_fleet(" not in f.read():
            raise BenchFailure(
                "this commit's ops/tree_kernel.py has no apply_nested_fleet "
                "(PR 28): its nested step takes ~20 s at this family's "
                "geometry on a v5e, and the cell cannot be served")


class TreeWriter:
    """An in-process SharedTree replica (EditManager + forest with the
    optimistic local branch) whose channel outbox is minted into the same
    UnsequencedMessage stream a wire client sends."""

    def __init__(self, client_id: str) -> None:
        self.client_id = client_id
        self.last_seq = 0
        self._client_seq = 0
        self._staged: list = []
        self.tree = SharedTreeChannel("t")
        shim = ChannelDeltaConnection(
            submit_fn=lambda contents, md=None, internal=False: (
                self._staged.append(contents)
            ),
            quorum_fn=lambda cid: 0,
            client_id_fn=lambda: client_id,
        )
        shim.connected = True
        self.tree.connect(shim)

    def process(self, msg) -> None:
        self.last_seq = msg.seq
        if msg.type != MessageType.OP:
            return
        self.tree.process_messages(MessageCollection(
            envelope=MessageEnvelope(
                client_id=msg.client_id, seq=msg.seq,
                min_seq=msg.min_seq, ref_seq=msg.ref_seq,
            ),
            messages=[ChannelMessage(
                contents=msg.contents,
                local=(msg.client_id == self.client_id),
            )],
        ))

    def take_outbox(self) -> list:
        out, self._staged = self._staged, []
        msgs = []
        for contents in out:
            self._client_seq += 1
            msgs.append(UnsequencedMessage(
                client_id=self.client_id, client_seq=self._client_seq,
                ref_seq=self.last_seq, type=MessageType.OP, contents=contents,
            ))
        return msgs

    @property
    def root(self) -> list:
        return self.tree.forest.root_field

    def root_json(self) -> list:
        return [n.to_json() for n in self.root]


class _Flush:
    """What the edits made on one document since its last flush have done to
    its root field, in the coordinates every replica shared when the flush
    before it returned (``base`` ids; -1 is a node made since).  ``ids[w]``
    mirrors writer w's local root field."""

    def __init__(self) -> None:
        self.ids: dict[int, list[int]] = {}
        self.removed: set[int] = set()
        self.touched: set[int] = set()


def _leaves(rng: random.Random, n: int) -> list:
    return [leaf(rng.randrange(1000)) for _ in range(n)]


def tree_edit(rng: random.Random, w: TreeWriter, ids: list[int],
              fl: _Flush, weights: dict) -> None:
    """One edit that always yields exactly ONE op row on the device:
    ``chip_smoke._tree_edit``'s four kinds (insert a leaf into the root
    field, insert a leaf under a root node's ``sub`` field, set a root
    node's value, remove a root node with its subtree) at the
    configuration's weights.  A remove takes a node no other edit of this
    flush has addressed, and no edit addresses a node this flush removes: a
    change rebased over the removal of its target is empty, and an empty
    commit is no device row."""
    t = w.tree
    n = len(ids)
    r = rng.random()
    a = weights["insert_root"]
    b = a + weights["insert_nested"]
    c = b + weights["set_value"]

    def pick(ok) -> int | None:
        # A node made since the last flush (-1) is its writer's alone.
        for _ in range(8):
            i = rng.randrange(n)
            if ids[i] < 0 or ok(ids[i]):
                return i
        return None

    i = None
    if n >= 4 and r >= a:
        if r < c:
            i = pick(lambda b_: b_ not in fl.removed)
        else:
            i = pick(lambda b_: b_ not in fl.removed
                     and b_ not in fl.touched)
    if i is None:
        i = rng.randint(0, n)
        t.submit_change(make_insert([], "", i, _leaves(rng, 1)))
        ids.insert(i, -1)
    elif r < b:
        t.submit_change(make_insert([("", i)], SUB_FIELD, 0, _leaves(rng, 1)))
        fl.touched.add(ids[i])
    elif r < c:
        t.submit_change(make_set_value([("", i)], rng.randrange(1000)))
        fl.touched.add(ids[i])
    else:
        t.submit_change(make_remove([], "", i, 1))
        fl.removed.add(ids[i])
        del ids[i]


class Plant:
    """The TCP front and sequencer (``NetworkServer``) and in-process
    SharedTree writers joined and submitting through the sequencer.

    A document's first edits are its FILL (``params``): ``churn_nodes``
    leaves appended to the root field and removed again by one op on each of
    the first ``churned_docs`` documents to be edited (a history that leaves
    dead rows, so that the fleet's compaction runs before the window), then
    ``root_nodes`` leaves into the root field and the rest of
    ``nodes_per_doc`` under root nodes' ``sub`` fields, ``fill_run`` leaves
    a row (the fleet's ``--max-insert-len``), all by the document's first
    writer.  Every edit after that is ``tree_edit``."""

    def __init__(self, seed: int, n_docs: int, params: dict) -> None:
        from fluidframework_tpu.server.netserver import NetworkServer

        require_servable_step()
        self.nodes_per_doc = int(params["nodes_per_doc"])
        self.root_nodes = int(params["root_nodes"])
        self.fill_run = int(params["fill_run"])
        self.churned_docs = int(params.get("churned_docs", 0))
        self.churn_nodes = int(params.get("churn_nodes", 0))
        self.weights = dict(params["weights"])
        if abs(sum(self.weights.values()) - 1.0) > 1e-9:
            raise BenchFailure(f"edit weights {self.weights} do not sum to 1")
        self.rng = random.Random(seed)
        self.doc_ids = [f"t{i}" for i in range(n_docs)]
        self.srv = NetworkServer().start()
        self.port = self.srv.port
        self.writers: dict[str, list[TreeWriter]] = {}
        self._fill: dict[str, list[tuple[str, int]]] = {}
        self._late: dict[str, int] = {}     # writers still to open the doc
        self._flushes: dict[str, _Flush] = {}
        self._turn: dict[str, int] = {}
        self.ops = 0           # OP messages sequenced == device op rows
        self.nacks = 0

    def _fill_plan(self, churned: bool) -> list[tuple[str, int]]:
        """A document's fill, last row first (``edit`` pops)."""
        def runs(kind: str, total: int) -> list[tuple[str, int]]:
            full, rest = divmod(total, self.fill_run)
            return [(kind, self.fill_run)] * full + (
                [(kind, rest)] if rest else [])

        plan: list[tuple[str, int]] = []
        if churned and self.churn_nodes:
            plan += runs("churn_in", self.churn_nodes)
            plan.append(("churn_out", self.churn_nodes))
        plan += runs("root", self.root_nodes)
        plan += runs(SUB_FIELD, self.nodes_per_doc - self.root_nodes)
        plan.reverse()
        return plan

    def fill_rows(self, churned: bool = False) -> int:
        """Rows (== edits) a document's fill takes."""
        return len(self._fill_plan(churned))

    def join(self, doc_id: str, n: int) -> None:
        """The document's FIRST writer joins now and makes the fill alone;
        the other ``n - 1`` open the document once the fill is sequenced
        (``_open_filled``), as clients that open an existing document do."""
        self._late[doc_id] = n - 1 if self.nodes_per_doc else 0
        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            have = self.writers.setdefault(doc_id, [])
            for _ in range(n - self._late[doc_id]):
                w = TreeWriter(f"{doc_id}-w{len(have)}")
                doc.connect(w.client_id, w.process)
                have.append(w)
            doc.process_all()

    def _open_filled(self, doc_id: str) -> None:
        """The rest of the document's writers boot from the first writer's
        summary (forest, EditManager window, id compressor: what the scribe
        would have written) and subscribe from the sequence number it
        covers, with no replay of the log."""
        ws = self.writers[doc_id]
        # Three replicas of 10,000 nodes are made here and stay for the
        # run: the collector is kept off them while they are built
        # (``flush`` freezes them right after).
        gc.disable()
        try:
            with self.srv.lock:
                doc = self.srv.service.document(doc_id)
                blob = json.dumps(ws[0].tree.summarize())
                for _ in range(self._late.pop(doc_id)):
                    w = TreeWriter(f"{doc_id}-w{len(ws)}")
                    w.tree.load(json.loads(blob))
                    _join, w.last_seq = doc.connect_stream(
                        w.client_id, w.process)
                    ws.append(w)
                doc.process_all()
        finally:
            gc.enable()

    def _fill_row(self, w: TreeWriter, kind: str, count: int) -> None:
        t, rng = w.tree, self.rng
        n = len(w.root)
        if kind == "churn_in":
            t.submit_change(make_insert([], "", n, _leaves(rng, count)))
        elif kind == "churn_out":
            t.submit_change(make_remove([], "", n - count, count))
        elif kind == "root":
            t.submit_change(make_insert(
                [], "", rng.randint(0, n), _leaves(rng, count)))
        else:
            t.submit_change(make_insert(
                [("", rng.randrange(n))], SUB_FIELD, 0, _leaves(rng, count)))

    def edit(self, doc_id: str) -> None:
        """One local edit, unsent until ``flush``: the next row of the
        document's fill while it has one (its first writer's, alone), else
        ``tree_edit`` on the document's next writer (round-robin), each
        against the state its writer has seen plus its own unsent edits."""
        ws = self.writers[doc_id]
        turn = self._turn.get(doc_id, 0)
        self._turn[doc_id] = turn + 1
        k = turn % len(ws)
        w = ws[k]
        plan = self._fill.get(doc_id)
        if plan is None:
            # The first ``churned_docs`` documents to be edited carry the
            # churn: the cell's ladder decides which they are.
            plan = self._fill[doc_id] = self._fill_plan(
                len(self._fill) < self.churned_docs)
        if plan:
            self._fill_row(w, *plan.pop())
            return
        fl = self._flushes.get(doc_id)
        if fl is None:
            fl = self._flushes[doc_id] = _Flush()
        ids = fl.ids.get(k)
        if ids is None:
            ids = fl.ids[k] = list(range(len(w.root)))
        tree_edit(self.rng, w, ids, fl, self.weights)

    def flush(self, doc_id: str) -> int:
        """Submit every writer's outbox, THEN deliver: ops of one round are
        concurrent (each stamped with the ref-seq its writer had seen).
        Returns the ops sequenced."""
        sent = 0
        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            for w in self.writers[doc_id]:
                for m in w.take_outbox():
                    if isinstance(doc.submit(m), Nack):
                        self.nacks += 1
                    else:
                        sent += 1
            doc.process_all()
        self._flushes.pop(doc_id, None)
        self.ops += sent
        if self._late.get(doc_id) and self._fill.get(doc_id) == []:
            self._open_filled(doc_id)
        # What an edit leaves behind stays for the run (four replicas'
        # logs and trunks): out of the collector's sight, as run.py freezes
        # the heap before the stream, or every full collection walks all
        # that the stream has made so far and the generator stalls for
        # hundreds of milliseconds late in a window (O(1): three lists
        # spliced; only cyclic garbage alive right now is kept for good).
        gc.freeze()
        return sent

    def drained(self, doc_ids, deadline: float) -> None:
        """Block until the front's writer tier has handed every byte for
        these documents to the kernel (nothing queued server-side)."""
        while any(self.srv.consumer_backlog(d) for d in doc_ids):
            if time.perf_counter() > deadline:
                raise BenchFailure("front never drained")
            time.sleep(0.01)

    def nodes(self, doc_id: str) -> int:
        """Live nodes of the document, by its first writer's replica."""
        def count(nodes) -> int:
            return sum(1 + sum(count(kids) for kids in n.fields.values())
                       for n in nodes)

        return count(self.writers[doc_id][0].root)

    def verify(self, final: dict, touched: list[str], first: list[str],
               sample_seed: int, budget_s: float, min_sample: int) -> dict:
        """``final`` is the child's ``done`` line.  Node for node, three
        ways: device tree JSON == the plain reference's replay of the
        sequencer log (``oracle_tree``) == every writer's ``root_json()``,
        for the documents in ``first`` and then the rest of ``touched`` in a
        seeded order, until all are done or, past ``first`` and
        ``min_sample`` more, the time budget runs out.  Untouched documents
        must be empty on the device, no document may have left the device
        path (``fallback_docs`` 0, ``device_fraction`` 1.0), and a document
        whose fill was not finished is a fault of the cell's ladder."""
        from fluidframework_tpu.loadgen.coordinator import oracle_tree

        h = final["health"]
        if h.get("fallback_docs") or h.get("device_fraction") != 1.0:
            return {"ok": False,
                    "why": f"fallback_docs {h.get('fallback_docs')}, "
                           f"device_fraction {h.get('device_fraction')}"}
        trees = final["trees"]
        touched_set = set(touched)
        for doc_id in self.doc_ids:
            if doc_id not in touched_set and trees.get(doc_id) != []:
                return {"ok": False, "why": f"{doc_id}: untouched doc has nodes"}
            if doc_id in touched_set and (
                    self._fill.get(doc_id) or self._late.get(doc_id)):
                return {"ok": False,
                        "why": f"{doc_id}: fill rows unsent: the cell's "
                               "ladder is too shallow"}
        rest = sorted(touched_set - set(first))
        random.Random(sample_seed).shuffle(rest)
        t0 = time.perf_counter()
        done = 0
        nodes = []
        for i, doc_id in enumerate(list(first) + rest):
            if (i >= len(first) + min_sample
                    and time.perf_counter() - t0 > budget_s):
                break
            with self.srv.lock:
                log = list(self.srv.service.document(doc_id).sequencer.log)
            # Through JSON, as the device's tree came.
            want = json.loads(json.dumps(oracle_tree(log)))
            for w in self.writers[doc_id]:
                if json.loads(json.dumps(w.root_json())) != want:
                    return {"ok": False,
                            "why": f"{doc_id}: writer {w.client_id} != oracle"}
            if trees.get(doc_id) != want:
                return {"ok": False, "why": f"{doc_id}: device tree != oracle"}
            nodes.append(self.nodes(doc_id))
            done += 1
        return {"ok": True, "verified": done, "touched": len(touched_set),
                "nodes_min": min(nodes, default=0),
                "nodes_max": max(nodes, default=0),
                "seconds": time.perf_counter() - t0}

    def stop(self) -> None:
        self.srv.stop()
