"""The SharedString family with its summary loop closed: the front and
sequencer (``NetworkServer``), in-process ``SharedString`` writers, and beside
them one summarizer client a document, whose ``summarize`` op the document's
own scribe acknowledges with a ``summaryAck`` in the document's total order.

``Plant`` / ``string_edit`` / the byte identity of ``verify`` are copies of
``plants/shared_string.py`` (itself a copy of ``chip_smoke.py``'s, PR 21); the
summarizer is ``chip_smoke.StringPlant.summarize`` run at a rate.  What
differs from ``plants/shared_string.py``:

- **The rule.**  After a ``flush``, a document with ``summary_max_ops`` or
  more OP messages sequenced since its last acked summary, and fewer than
  ``summaries_in_flight_per_doc`` summaries in flight, summarises: upload,
  a sequenced ``SUMMARIZE`` op, and the scribe's ``summaryAck``
  (``LocalDocument._scribe_process_summarize`` -> ``Sequencer.mint_service``),
  all under the front's lock, so the ack follows the flush's ops on the
  document's firehose.  That is upstream's ``RunningSummarizer`` on
  ``ISummaryConfiguration.maxOps`` alone; its time- and idle-driven summaries
  are left out (the configuration says why).
- **What is an op row.**  ``ops`` counts OP messages, one device row each, as
  before.  The summarizer's join, ``SUMMARIZE`` and ``summaryAck`` are
  sequenced, reach the fleet and move its collab window's floor, but are no
  rows: the harness holds a run to ``rows == ops``.
- **What the upload holds.**  The empty tree ``chip_smoke`` uploads.  What a
  summary contains is the summarizer's and the scribe's cost, on the host side
  of the front; the device tier is measured on what the ack makes it do
  (zamboni at the floor the ack carries), and that does not depend on it.
- **Obliterates.**  A document's obliterates are no longer capped for life but
  by how many are outstanding: sequenced above the ``min_seq`` stamp of the
  document's last-but-one ``summaryAck`` (one ack of slack: the plant sees an
  ack when it is minted, the device frees the records a loop or a stall
  later), plus those not yet flushed.  At ``max_obliterates_outstanding``
  further draws are removes, as in the old plant.
- **verify.**  The old three-way byte identity, and three numbers, each exact
  (limit 0) and each named in ``why`` when it fails: ``acks_unhonoured``
  (documents whose device ``min_seq``, from the ``done`` line, is below the
  stamp of the last ack the fleet saw), ``evictable_left`` (the ``done``
  line's count of segments zamboni would still drop) and ``acks`` > 0.  An ack
  the fleet had not read when it applied its last planned row is not held
  against it, one a document at most (``acks_unseen_docs`` counts the
  documents with more).

Nothing here imports JAX.
"""

from __future__ import annotations

import random
import time

from child import BenchFailure


def _word(rng: random.Random) -> str:
    return "".join(rng.choice("abcdefghij") for _ in range(rng.randint(1, 8)))


def string_edit(rng: random.Random, c, obliterate: bool = True) -> bool:
    """One edit that always yields exactly ONE op row on the device: inserts
    stay within fleet_main's default --max-insert-len (8) and annotates carry
    one property.  About 50% insert of 1-8 chars, 10% sided obliterate, 18%
    remove, 22% annotate once the text has 12 characters.  Returns whether
    the edit was an obliterate."""
    n = len(c.text)
    r = rng.random()
    if n < 12 or r < 0.5:
        c.insert_text(rng.randint(0, n), _word(rng))
    elif obliterate and r < 0.6:
        p = rng.randint(1, n - 6)
        c.obliterate_range_sided((p, True), (p + rng.randint(1, 3), False))
        return True
    elif r < 0.78:
        p = rng.randint(0, n - 3)
        c.remove_range(p, p + rng.randint(1, 2))
    else:
        p = rng.randint(0, n - 4)
        c.annotate_range(p, p + rng.randint(1, 3), rng.choice((1, 2, 3)),
                         rng.randint(1, 99))
    return False


class Plant:
    """The TCP front and sequencer (``NetworkServer``), in-process
    SharedString writers joined and submitting through the sequencer, and a
    summarizer client for every document that has gathered enough ops."""

    def __init__(self, seed: int, n_docs: int, params: dict) -> None:
        from fluidframework_tpu.server.netserver import NetworkServer

        self.summary_max_ops = int(params["summary_max_ops"])
        self.max_in_flight = int(params["summaries_in_flight_per_doc"])
        # An obliterate holds one of the document's ob_slots (8 at
        # fleet_main's geometry) until the compaction of a summary ack
        # whose floor has passed it.
        self.max_obliterates = int(params["max_obliterates_outstanding"])
        self.ob_unsent: dict[str, int] = {}        # edited, not yet flushed
        self.ob_seqs: dict[str, list[int]] = {}    # sequenced, maybe live
        self.ob_total: dict[str, int] = {}         # sequenced, for life
        self.rng = random.Random(seed)
        self.doc_ids = [f"s{i}" for i in range(n_docs)]
        self.srv = NetworkServer().start()
        self.port = self.srv.port
        self.writers: dict[str, list] = {}
        self._turn: dict[str, int] = {}
        self.ops = 0           # OP messages sequenced == device op rows
        self.nacks = 0
        # The summary loop, per document: OP messages since the last acked
        # summary, summaries in flight, the summarizer's client sequence
        # number (its presence: the summarizer has joined), and the min_seq
        # stamp of every summaryAck the sequencer logged, in order.
        self.since_summary: dict[str, int] = {}
        self.in_flight: dict[str, int] = {}
        self._summarizer_seq: dict[str, int] = {}
        self.ack_stamps: dict[str, list[int]] = {}

    def join(self, doc_id: str, n: int) -> None:
        from fluidframework_tpu.dds.shared_string import SharedString

        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            have = self.writers.setdefault(doc_id, [])
            for _ in range(n):
                c = SharedString(client_id=f"{doc_id}-w{len(have)}")
                doc.connect(c.client_id, c.process)
                have.append(c)
            doc.process_all()

    def _outstanding(self, doc_id: str) -> int:
        """Obliterates that may still hold a record on the device: unsent,
        or sequenced above the stamp of the last-but-one ack."""
        stamps = self.ack_stamps.get(doc_id, ())
        floor = stamps[-2] if len(stamps) >= 2 else 0
        seqs = self.ob_seqs.get(doc_id)
        if seqs and seqs[0] <= floor:
            seqs[:] = [s for s in seqs if s > floor]
        return self.ob_unsent.get(doc_id, 0) + len(seqs or ())

    def edit(self, doc_id: str) -> None:
        """One local edit on the document's next writer (round-robin); it
        stays in the writer's outbox until ``flush``."""
        ws = self.writers[doc_id]
        i = self._turn.get(doc_id, 0)
        self._turn[doc_id] = i + 1
        if string_edit(
                self.rng, ws[i % len(ws)],
                obliterate=self._outstanding(doc_id) < self.max_obliterates):
            self.ob_unsent[doc_id] = self.ob_unsent.get(doc_id, 0) + 1

    def flush(self, doc_id: str) -> int:
        """Submit every writer's outbox, THEN deliver: ops of one round are
        concurrent (each stamped with the ref-seq its writer had seen).  Then
        the document summarises if its rule says so.  Returns the ops
        sequenced."""
        from fluidframework_tpu.protocol.messages import DeltaType, Nack

        sent = 0
        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            for c in self.writers[doc_id]:
                for m in c.take_outbox():
                    out = doc.submit(m)
                    if isinstance(out, Nack):
                        self.nacks += 1
                        continue
                    sent += 1
                    if out.contents["type"] == DeltaType.OBLITERATE_SIDED:
                        self.ob_seqs.setdefault(doc_id, []).append(out.seq)
                        self.ob_total[doc_id] = (
                            self.ob_total.get(doc_id, 0) + 1)
            self.ob_unsent[doc_id] = 0
            doc.process_all()
            self.ops += sent
            gathered = self.since_summary.get(doc_id, 0) + sent
            self.since_summary[doc_id] = gathered
            if (gathered >= self.summary_max_ops
                    and self.in_flight.get(doc_id, 0) < self.max_in_flight):
                self._summarize(doc_id, doc)
        return sent

    def _summarize(self, doc_id: str, doc) -> None:
        """The summarizer's voice and the scribe's (held: the front's lock):
        upload, a sequenced ``SUMMARIZE`` op at the current sequence number,
        and the ``summaryAck`` the document's scribe mints on delivering it.
        None of the three is an op row."""
        from fluidframework_tpu.protocol.messages import (
            MessageType,
            Nack,
            UnsequencedMessage,
        )

        client = f"{doc_id}-summarizer"
        if doc_id not in self._summarizer_seq:
            doc.connect(client, lambda m: None)
            doc.process_all()
            self._summarizer_seq[doc_id] = 0
        self._summarizer_seq[doc_id] += 1
        at = doc.sequencer.seq
        handle = doc.upload_summary({"type": "tree", "entries": {}})
        logged = len(doc.sequencer.log)
        out = doc.submit(UnsequencedMessage(
            client_id=client, client_seq=self._summarizer_seq[doc_id],
            ref_seq=at, type=MessageType.SUMMARIZE,
            contents={"handle": handle, "refSeq": at},
        ))
        if isinstance(out, Nack):
            self.nacks += 1
            return
        self.in_flight[doc_id] = self.in_flight.get(doc_id, 0) + 1
        doc.process_all()
        for m in doc.sequencer.log[logged:]:
            if m.type == MessageType.SUMMARY_ACK:
                # Every op so far is at or below the summary's refSeq.
                self.ack_stamps.setdefault(doc_id, []).append(int(m.min_seq))
                self.in_flight[doc_id] -= 1
                self.since_summary[doc_id] = 0
            elif m.type == MessageType.SUMMARY_NACK:
                self.in_flight[doc_id] -= 1
                self.nacks += 1

    def drained(self, doc_ids, deadline: float) -> None:
        """Block until the front's writer tier has handed every byte for
        these documents to the kernel (nothing queued server-side)."""
        while any(self.srv.consumer_backlog(d) for d in doc_ids):
            if time.perf_counter() > deadline:
                raise BenchFailure("front never drained")
            time.sleep(0.01)

    def verify(self, final: dict, touched: list[str], first: list[str],
               sample_seed: int, budget_s: float, min_sample: int) -> dict:
        """``final`` is the child's ``done`` line.  Byte identity three ways
        — device text == host-oracle replay of the sequencer log == every
        writer's replica — for the documents in ``first`` and then the rest
        of ``touched`` in a seeded order, until all are done or, past
        ``first`` and ``min_sample`` more, the time budget runs out;
        untouched documents must be empty on the device.  Then the window's
        guarantee, over every document (see the module's docstring)."""
        from fluidframework_tpu.loadgen.coordinator import oracle_text

        texts = final["texts"]
        touched_set = set(touched)
        for doc_id in self.doc_ids:
            if doc_id not in touched_set and texts.get(doc_id) != "":
                return {"ok": False, "why": f"{doc_id}: untouched doc has text"}
        rest = sorted(touched_set - set(first))
        random.Random(sample_seed).shuffle(rest)
        t0 = time.perf_counter()
        done = 0
        for i, doc_id in enumerate(list(first) + rest):
            if (i >= len(first) + min_sample
                    and time.perf_counter() - t0 > budget_s):
                break
            with self.srv.lock:
                log = list(self.srv.service.document(doc_id).sequencer.log)
            want = oracle_text(log)
            for c in self.writers[doc_id]:
                if c.text != want:
                    return {"ok": False,
                            "why": f"{doc_id}: writer {c.client_id} != oracle"}
            if texts.get(doc_id) != want:
                return {"ok": False, "why": f"{doc_id}: device text != oracle"}
            done += 1
        out = {"verified": done, "touched": len(touched_set),
               "seconds": time.perf_counter() - t0}
        if any(k not in final
               for k in ("min_seqs", "acks_seen", "evictable_left")):
            return {**out, "ok": False, "why": (
                "the done line carries no min_seqs / acks_seen / "
                "evictable_left: this commit's fleet has no per-document "
                "compaction (DocBatchEngine.compact(docs), PR 35) and cannot "
                "be held to the configuration's window guarantee")}
        out.update(self._window_check(final))
        problems = [
            f"{k} = {out[k]}"
            for k in ("acks_unhonoured", "evictable_left", "acks_unseen_docs")
            if out[k]
        ]
        if not out["acks"]:
            problems.append("acks = 0: the cell's summary loop never ran")
        return {**out, "ok": not problems, "why": "; ".join(problems)}

    def _window_check(self, final: dict) -> dict:
        """The ``window`` guarantee as far as the ``done`` line shows it."""
        unhonoured = unseen = 0
        for i, doc_id in enumerate(self.doc_ids):
            stamps = self.ack_stamps.get(doc_id, [])
            seen = int(final["acks_seen"][i])
            if not 0 <= len(stamps) - seen <= 1:
                unseen += 1
            elif seen and final["min_seqs"][i] < stamps[seen - 1]:
                unhonoured += 1
        return {
            "acks": sum(len(v) for v in self.ack_stamps.values()),
            "acks_seen_by_fleet": int(sum(final["acks_seen"])),
            "acked_docs": len(self.ack_stamps),
            "acks_unhonoured": unhonoured,
            "acks_unseen_docs": unseen,
            "evictable_left": int(final["evictable_left"]),
            "obliterates": sum(self.ob_total.values()),
            "most_obliterates_in_a_doc": max(self.ob_total.values(),
                                             default=0),
        }

    def stop(self) -> None:
        self.srv.stop()
