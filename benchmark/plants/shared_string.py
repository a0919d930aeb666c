"""The SharedString family: the TCP front and sequencer (``NetworkServer``),
in-process ``SharedString`` writers, one edit, and the comparison that decides
``correct``.

``Plant`` / ``string_edit`` / ``verify`` are copies of ``chip_smoke.py``'s
``StringPlant`` / ``_string_edit`` / ``verify`` (PR 21, sound on the chip).
Differences from the original: a nack is counted instead of raised, ``verify``
can stop at a time budget, and a document gets at most
``max_obliterates_per_doc`` obliterates (the configuration's ``plant.params``).

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
    """The TCP front and sequencer (``NetworkServer``) and in-process
    SharedString writers joined and submitting through the sequencer."""

    def __init__(self, seed: int, n_docs: int, params: dict) -> None:
        from fluidframework_tpu.server.netserver import NetworkServer

        # An obliterate holds one of the document's ob_slots (8 at
        # fleet_main's geometry) until a compaction expires it; no cell
        # carries a summary ack, so a document gets at most that many.
        self.max_obliterates = int(params["max_obliterates_per_doc"])
        self.obliterates: dict[str, int] = {}
        self.rng = random.Random(seed)
        self.doc_ids = [f"s{i}" for i in range(n_docs)]
        self.srv = NetworkServer().start()
        self.port = self.srv.port
        self.writers: dict[str, list] = {}
        self._turn: dict[str, int] = {}
        self.ops = 0           # OP messages sequenced == device op rows
        self.nacks = 0

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

    def edit(self, doc_id: str) -> None:
        """One local edit on the document's next writer (round-robin); it
        stays in the writer's outbox until ``flush``."""
        ws = self.writers[doc_id]
        i = self._turn.get(doc_id, 0)
        self._turn[doc_id] = i + 1
        n_ob = self.obliterates.get(doc_id, 0)
        if string_edit(self.rng, ws[i % len(ws)],
                       obliterate=n_ob < self.max_obliterates):
            self.obliterates[doc_id] = n_ob + 1

    def flush(self, doc_id: str) -> int:
        """Submit every writer's outbox, THEN deliver: ops of one round are
        concurrent (each stamped with the ref-seq its writer had seen).
        Returns the ops sequenced."""
        from fluidframework_tpu.protocol.messages import Nack

        sent = 0
        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            for c in self.writers[doc_id]:
                for m in c.take_outbox():
                    if isinstance(doc.submit(m), Nack):
                        self.nacks += 1
                    else:
                        sent += 1
            doc.process_all()
        self.ops += sent
        return sent

    def drained(self, doc_ids, deadline: float) -> None:
        """Block until the front's writer tier has handed every byte for
        these documents to the kernel (nothing queued server-side)."""
        while any(self.srv.consumer_backlog(d) for d in doc_ids):
            if time.perf_counter() > deadline:
                raise BenchFailure("front never drained")
            time.sleep(0.01)

    def verify(self, final: dict, touched: list[str], first: list[str],
               sample_seed: int, budget_s: float, min_sample: int) -> dict:
        """``final`` is the child's ``done`` line.  Byte identity three ways — device text == host-oracle replay of
        the sequencer log == every writer's replica — for the documents in
        ``first`` and then the rest of ``touched`` in a seeded order, until
        all are done or, past ``first`` and ``min_sample`` more, the time
        budget runs out.  Untouched documents must be empty on the device."""
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
        return {"ok": True, "verified": done, "touched": len(touched_set),
                "seconds": time.perf_counter() - t0}

    def stop(self) -> None:
        self.srv.stop()
