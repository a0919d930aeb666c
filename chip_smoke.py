"""chip_smoke.py — does the served system still start on the chip?

Drives the main path once, through the entry points a deployment uses: a
``NetworkServer`` front, ``SharedString`` / SharedTree writers submitting
through the sequencer, and the device tier as a REAL
``python -m fluidframework_tpu.server.fleet_main`` subprocess that
subscribes to the firehose, decodes through ``native/ingest.cpp``, applies
on the device and prints its JSON status lines.  Four legs, one child
process each, one after another (a chip belongs to one process at a time),
all sharing one compile cache:

  a. the string fleet at ``BASELINE.json`` config-3 size, cold
  b. the same command again, warm: it must find the compile cache and
     still be byte-identical (every fleet program donates its state, and
     the executables are now reloaded from disk)
  c. ``fleet_main --family tree``: 256 documents x 16,384 nodes
  d. the jitted programs neither fleet reaches (map, matrix, the batched
     tree rebase window, the Pallas position resolve), each once at bench
     shape against its host oracle

What comes out is checked by the repo's own means: final texts/trees
byte-equal the writers' replicas AND a host-oracle replay of each
sequencer log, and no lane that lets the device give way to the host may
have been used (quarantine, oracle, overflow, Python ingest, tree
fallback).  This parent never imports JAX.

    python3 chip_smoke.py                  # the check; needs an accelerator
    python3 chip_smoke.py --mesh 4 --legs a,b    # string legs on 4 chips
    python3 chip_smoke.py --rehearse-cpu   # tiny CPU rehearsal, never a pass

The last line of stdout is the verdict, one JSON object with exactly two
keys, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``"ok":
true`` only for the whole smoke on an accelerator.  The line above it is
the report (legs, sizes, ``reduced``, seconds).  With no accelerator it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import queue
import random
import resource
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LEGS = ("a", "b", "c", "d")
HOT_DOCS = 8          # documents every wave returns to
STATUS_EVERY_S = 0.5  # fleet status cadence: the applied-rows barrier
LEG_DEADLINE_S = 600.0
RUN_DEADLINE_S = 1150.0  # the whole smoke, compilation included

# BASELINE.json config 3 asks for 10,000 SharedString documents.  At
# fleet_main's default geometry (4,096 segments x 65,536 chars) that is
# 5.8 GiB of state, which fits a 16 GB v5e — but the fleet-step program
# does not: XLA's buffer assignment for jit(_fleet_step) asks for ~1.35 MiB
# of HLO temporaries per document on top of the 0.59 MiB of state (whole
# [D, 4096] columns copied across the obliterate lax.cond and the scan
# carry), measured as 3.65 GiB in use + 8.29 GiB reserved at D = 6,144 and
# a compile-time RESOURCE_EXHAUSTED at D = 10,000 (PERF.md, PR 21).  Add
# the [K, D, 32, 8] staging uploads, lane-padded 16x on device, and 7,168
# documents (7 x 1,024) is what one chip holds with a margin.  A mesh of
# four chips holds the stated 10,000 (2,500 each).
CHIP_DOCS_WANTED = 10_000
CHIP_DOCS_ONE_CHIP = 7_168

SIZES = {
    # fleet_main's own defaults: no geometry flag is passed on the chip.
    "chip": {
        "string_docs": CHIP_DOCS_ONE_CHIP, "string_flags": [],
        "tree_docs": 256, "tree_flags": ["--capacity", "16384"],
        "kernels": {"map_rounds": 16, "matrix_side": 128,
                    "rebase_windows": 256, "pallas_segments": 262_144},
    },
    "rehearsal": {
        "string_docs": 64,
        "string_flags": ["--capacity", "1024", "--text-capacity", "8192"],
        "tree_docs": 8, "tree_flags": ["--capacity", "512"],
        "kernels": {"map_rounds": 4, "matrix_side": 16,
                    "rebase_windows": 16, "pallas_segments": 4096},
    },
}


class SmokeFailure(Exception):
    """A check failed or a child misbehaved; the message says which."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ children
class Child:
    """One child process that owns the chip: stdout drained by a thread
    (a full pipe must never stall the fleet), stderr kept in a file."""

    def __init__(self, name: str, cmd: list[str], env: dict, workdir: str):
        self.name = name
        self.err_path = os.path.join(workdir, f"{name}.stderr")
        self._err = open(self.err_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=self._err, text=True,
            env=env, cwd=REPO,
        )
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def next_json(self, deadline: float, what: str) -> dict:
        """The next JSON object the child printed (non-JSON lines skipped)."""
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise SmokeFailure(f"{self.name}: timed out waiting for {what}")
            try:
                line = self._lines.get(timeout=min(remaining, 1.0))
            except queue.Empty:
                continue
            if line is None:
                raise SmokeFailure(
                    f"{self.name}: exited (code {self.proc.wait()}) while "
                    f"waiting for {what}\n{self.stderr_tail()}"
                )
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                return obj

    def wait_for(self, pred, deadline: float, what: str) -> dict:
        while True:
            obj = self.next_json(deadline, what)
            if "error" in obj and "health" not in obj:
                raise SmokeFailure(f"{self.name}: {obj}")
            if pred(obj):
                return obj

    def finish(self, deadline: float) -> None:
        try:
            rc = self.proc.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name}: did not exit") from None
        check(rc == 0, f"{self.name}: exit code {rc}\n{self.stderr_tail()}")

    def stderr_tail(self, n: int = 3000) -> str:
        self._err.flush()
        with open(self.err_path) as f:
            return f.read()[-n:]

    def kill(self) -> None:
        if self.proc.poll() is None:
            with contextlib.suppress(OSError):
                os.kill(self.proc.pid, signal.SIGCONT)
            self.proc.kill()
            self.proc.wait()
        self._err.close()


def probe_device(env: dict) -> dict:
    """What JAX finds, asked in a throwaway child (this parent stays off
    JAX so that every leg's child can own the chip)."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); print(json.dumps({"
         "'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if r.returncode != 0:
        raise SmokeFailure(f"device probe failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def verdict_line(ok: bool, device: dict) -> dict:
    """The last line of stdout: exactly ``ok`` and ``device``, the device
    exactly ``platform`` / ``kind`` (text) and ``count`` (a whole number)."""
    return {
        "ok": bool(ok),
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    }


# ------------------------------------------------------------ string traffic
def _word(rng: random.Random) -> str:
    return "".join(rng.choice("abcdefghij") for _ in range(rng.randint(1, 8)))


def _string_edit(rng: random.Random, c, obliterate: bool = False) -> None:
    """One edit that always yields exactly ONE op row on the device:
    inserts stay within fleet_main's default --max-insert-len (8) and
    annotates carry one property."""
    n = len(c.text)
    r = rng.random()
    if n < 12 or r < 0.5:
        c.insert_text(rng.randint(0, n), _word(rng))
    elif obliterate and r < 0.6:
        p = rng.randint(1, n - 6)
        c.obliterate_range_sided((p, True), (p + rng.randint(1, 3), False))
    elif r < 0.78:
        p = rng.randint(0, n - 3)
        c.remove_range(p, p + rng.randint(1, 2))
    else:
        p = rng.randint(0, n - 4)
        c.annotate_range(p, p + rng.randint(1, 3), rng.choice((1, 2, 3)),
                         rng.randint(1, 99))


class StringPlant:
    """The host half of a string leg: the TCP front and in-process
    SharedString writers joined and submitting through the sequencer."""

    def __init__(self, seed: int, n_docs: int) -> None:
        from fluidframework_tpu.server.netserver import NetworkServer

        self.rng = random.Random(seed)
        self.doc_ids = [f"s{i}" for i in range(n_docs)]
        self.srv = NetworkServer().start()
        self.writers: dict[str, list] = {}
        self.ops = 0           # OP messages sequenced == device op rows
        self.obliterates = 0

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

    def flush(self, doc_id: str) -> None:
        """Submit every writer's outbox, THEN deliver: ops of one round are
        concurrent (each stamped with the ref-seq its writer had seen)."""
        from fluidframework_tpu.protocol.messages import Nack

        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            for c in self.writers[doc_id]:
                for m in c.take_outbox():
                    res = doc.submit(m)
                    check(not isinstance(res, Nack), f"{doc_id}: nack {res}")
                    self.ops += 1
                    self.obliterates += m.contents["type"] in (4, 5)
            doc.process_all()

    def hot_round(self, doc_id: str, edits_per_writer: int,
                  obliterate: bool = False) -> None:
        for c in self.writers[doc_id]:
            for _ in range(edits_per_writer):
                _string_edit(self.rng, c, obliterate)
        self.flush(doc_id)

    def swallow(self, doc_id: str) -> None:
        """SKILL.md "Probes that matter": a sided obliterate with a
        concurrent insert INSIDE its range — the insert must be swallowed
        on every replica and on the device."""
        a, b = self.writers[doc_id][:2]
        check(len(a.text) >= 8, f"{doc_id}: too short for the swallow probe")
        a.obliterate_range_sided((1, True), (5, False))
        b.insert_text(3, "SWALLOW")
        self.flush(doc_id)
        check("SWALLOW" not in a.text and a.text == b.text,
              f"{doc_id}: writers disagree on the swallow case")

    def summarize(self, doc_id: str) -> None:
        """The scribe's voice: a summarize op whose ack carries the MSN —
        the firehose signal on which FleetConsumer compacts the fleet."""
        from fluidframework_tpu.protocol.messages import (
            MessageType,
            UnsequencedMessage,
        )

        with self.srv.lock:
            doc = self.srv.service.document(doc_id)
            handle = doc.upload_summary({"type": "tree", "entries": {}})
            doc.connect("scriber", lambda m: None)
            doc.process_all()
            doc.submit(UnsequencedMessage(
                client_id="scriber", client_seq=1,
                ref_seq=doc.sequencer.seq, type=MessageType.SUMMARIZE,
                contents={"handle": handle, "refSeq": doc.sequencer.seq},
            ))
            doc.process_all()

    def drained(self, doc_ids, deadline: float) -> None:
        """Block until the front's writer tier has handed every byte for
        these documents to the kernel (nothing queued server-side)."""
        while any(self.srv.consumer_backlog(d) for d in doc_ids):
            check(time.monotonic() < deadline, "front never drained")
            time.sleep(0.01)

    def verify(self, texts: dict) -> int:
        """Byte identity, three ways, for every document that got an op."""
        from fluidframework_tpu.loadgen.coordinator import oracle_text

        for doc_id in self.doc_ids:
            writers = self.writers.get(doc_id)
            if not writers:
                check(texts[doc_id] == "", f"{doc_id}: untouched doc has text")
                continue
            with self.srv.lock:
                log = list(self.srv.service.document(doc_id).sequencer.log)
            want = oracle_text(log)
            for c in writers:
                check(c.text == want, f"{doc_id}: writer {c.client_id} != oracle")
            check(texts[doc_id] == want,
                  f"{doc_id}: device text != oracle\n  device {texts[doc_id]!r}"
                  f"\n  oracle {want!r}")
        return len(self.writers)

    def stop(self) -> None:
        self.srv.stop()


class Gate:
    """Deliver one burst of traffic to the fleet ATOMICALLY.

    The fleet picks its program from how many documents are busy in one
    pump (cohort buckets by power of two, full-fleet above a quarter of
    the fleet) and how deep their queues are (megastep K).  A live
    consumer reads whatever has arrived, so the same burst would compile
    different shapes from run to run — and the warm leg could not be held
    to "no new compiles".  So: wait until the fleet has APPLIED everything
    sent so far (its status line reports rows after each step), stop the
    process the way a descheduled consumer stops, let the front hand the
    whole burst to the kernel's socket buffers, and continue it: the next
    pump sees every socket ready at once."""

    def __init__(self, child: Child, plant: StringPlant) -> None:
        self.child, self.plant = child, plant
        self.bursts: list[dict] = []   # what was sent, and how long it took
        self._released = child.t_spawn

    def applied(self) -> None:
        """The fleet reported the last burst applied: close its clock
        (host seconds from release to the status line, 0.5 s grain)."""
        if self.bursts:
            self.bursts[-1]["applied_seconds"] = round(
                time.monotonic() - self._released, 1
            )

    def burst(self, what: str, doc_ids, send, deadline: float,
              after=None) -> None:
        """``after(status)`` says the previous burst is applied; by
        default: the fleet has stepped every row sent so far."""
        sent = self.plant.ops
        self.child.wait_for(
            lambda s: "health" in s and (
                after(s) if after else s.get("rows", -1) >= sent
            ),
            deadline, f"the previous burst to be applied before {what}",
        )
        self.applied()
        t0 = time.monotonic()
        os.kill(self.child.proc.pid, signal.SIGSTOP)
        try:
            send()
            self.plant.drained(doc_ids, deadline)
        finally:
            os.kill(self.child.proc.pid, signal.SIGCONT)
        self._released = time.monotonic()
        self.bursts.append({
            "burst": what, "docs": len(doc_ids),
            "ops": self.plant.ops - sent,
            "send_seconds": round(self._released - t0, 1),
        })
        say(f"  burst {what}: {self.plant.ops - sent} ops into "
            f"{len(doc_ids)} docs")


def _cache_files(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def string_leg(tag: str, size: dict, args, env: dict, workdir: str,
               device: dict, deadline: float) -> dict:
    from fluidframework_tpu.utils import compile_cache

    n_docs = size["string_docs"]
    plant = StringPlant(args.seed, n_docs)
    hot = plant.doc_ids[:HOT_DOCS]
    # More than a quarter of the fleet busy in ONE pump is what makes
    # _step_fleet take the fleet-wide program instead of a cohort.
    wide = plant.doc_ids[HOT_DOCS:HOT_DOCS + n_docs // 4 + n_docs // 16 + 1]
    child = None
    cache_dir = compile_cache.cache_dir()
    default_dir = os.path.join(REPO, ".jax_compile_cache")
    files_before = _cache_files(cache_dir)
    default_before = _cache_files(default_dir)
    try:
        # wave 0, before the fleet attaches (catch-up path): 2 writers on
        # each hot doc, concurrent insert/remove/annotate/obliterate with
        # real ref-seq lag, plus the swallow case.  <= 32 rows per doc:
        # one K=1 cohort dispatch.
        for d in hot:
            plant.join(d, 2)
            for _ in range(3):
                plant.hot_round(d, 4, obliterate=True)
            plant.swallow(d)
        for d in wide:
            plant.join(d, 1)
        wave0 = plant.ops
        planned = (wave0 + len(wide) * (1 + 36)
                   + len(hot) * (4 * 3 * 2 + 4 * 2))
        cmd = [
            sys.executable, "-m", "fluidframework_tpu.server.fleet_main",
            "--port", str(plant.srv.port), "--docs", ",".join(plant.doc_ids),
            "--exit-after-rows", str(planned),
            "--status-every", str(STATUS_EVERY_S),
            *size["string_flags"],
        ]
        if args.mesh:
            cmd += ["--mesh", str(args.mesh)]
        child = Child(f"string-{tag}", cmd, env, workdir)
        ready = child.wait_for(lambda s: s.get("ready"), deadline, "readiness")
        setup_s = time.monotonic() - child.t_spawn
        say(f"  ready in {setup_s:.1f}s on {ready['platform']} "
            f"{ready['device_kind']} x{ready['device_count']}, resident "
            f"{ready['resident_bytes_per_device']}")
        check(ready["platform"] == device["platform"],
              f"fleet runs on {ready['platform']}, probe saw {device}")
        gate = Gate(child, plant)

        # wave 1, live: ONE op into each of > D/4 documents (the K=1
        # fleet-wide step), then 36 more into each (two 32-row slices
        # queued fleet-wide: the [K=2, D, 32] megastep).
        def one_each():
            for d in wide:
                _string_edit(plant.rng, plant.writers[d][0])
                plant.flush(d)

        def deep():
            for d in wide:
                for _ in range(36):
                    _string_edit(plant.rng, plant.writers[d][0])
                plant.flush(d)

        gate.burst("1A: 1 op x wide", wide, one_each, deadline)
        gate.burst("1B: 36 ops x wide", wide, deep, deadline)

        # wave 2: the hot docs again, now 4 writers each (cohort
        # gather/step/scatter), then the summary ack that compacts the
        # fleet, then a last round so that the ack is not the end.
        for d in hot:
            plant.join(d, 2)

        def hot_again():
            for d in hot:
                for _ in range(2):
                    plant.hot_round(d, 3, obliterate=True)

        gate.burst("2A: 4 writers x hot", hot, hot_again, deadline)
        gate.burst("2-ack: summarize, fleet compacts", hot[:1],
                   lambda: plant.summarize(hot[0]), deadline)
        gate.burst("2B: last round + final readback", hot,
                   lambda: [plant.hot_round(d, 2) for d in hot], deadline,
                   after=lambda s: s["health"].get("msn_compactions"))
        check(plant.ops == planned,
              f"traffic plan drifted: sent {plant.ops}, planned {planned}")

        final = child.wait_for(lambda s: s.get("done"), deadline, "final line")
        gate.applied()
        child.finish(deadline)
        waves_s = time.monotonic() - child.t_spawn - setup_s
        h = final["health"]
        verified = plant.verify(final["texts"])

        # No lane that lets the device give way to the host.
        for k in ("quarantined_docs", "oracle_docs", "overflow_docs"):
            check(h[k] == 0, f"{k} = {h[k]}")
        check(final["errors"] == 0, f"errors = {final['errors']}")
        check(not h.get("ingest_fallback_msgs"), "per-message ingest was used")
        check(h["ingest_plane"] == "native",
              f"ingest plane {h['ingest_plane']}, not native")
        check(h["megastep_dispatches"] > 0, "no dispatch")
        check(h["full_steps"] > 0, "the fleet-wide step never ran")
        if args.mesh:
            # bucketing is off under a mesh: every wave is a fleet step.
            check(h["cohort_steps"] == 0, "cohort step under a mesh")
            per_dev = list(ready["resident_bytes_per_device"].values())
            check(len(per_dev) == args.mesh,
                  f"{len(per_dev)} devices hold state, wanted {args.mesh}")
            check(max(per_dev) <= 1.03 * min(per_dev),
                  f"uneven shards: {ready['resident_bytes_per_device']}")
        else:
            check(h["cohort_steps"] > 0, "the cohort step never ran")
        check(h.get("msn_compactions", 0) >= 1, "no compaction ran")
        check(plant.obliterates > 0, "no obliterate was sequenced")
        with plant.srv.lock:
            msn = plant.srv.service.document(hot[0]).sequencer.min_seq
        check(msn > 0, "MSN never advanced")
        files_added = _cache_files(cache_dir) - files_before
        out = {
            "docs": n_docs,
            "resident_bytes_per_device": ready["resident_bytes_per_device"],
            "mesh": args.mesh,
            "setup_seconds": round(setup_s, 1),
            "waves_seconds": round(waves_s, 1),
            "bursts": gate.bursts,
            "compile": final["compile"],
            "cache_files_added": files_added,
            "ops_applied": plant.ops,
            "obliterates_applied": plant.obliterates,
            "docs_verified": verified,
            "full_steps": h["full_steps"],
            "cohort_steps": h["cohort_steps"],
            "megastep_dispatches": h["megastep_dispatches"],
            "msn_compactions": h["msn_compactions"],
            "recompiles": h["recompiles"],
            "despecializations": h["despecializations"],
            # Reuse barrier of the staging ring: on a device with real
            # transfers no upload may alias the host buffer.
            "staging_overlap_packs": h["staging_overlap_packs"],
            "staging_aliased_swaps": h["staging_aliased_swaps"],
        }
        if os.path.realpath(default_dir) != os.path.realpath(cache_dir):
            # The cache was placed from outside (JAX_COMPILATION_CACHE_DIR):
            # nothing may have written the in-checkout default instead.
            added = _cache_files(default_dir) - default_before
            check(added == 0, f"{added} files written to {default_dir} "
                              f"although the cache is {cache_dir}")
        return out
    finally:
        if child is not None:
            child.kill()
        plant.stop()


# -------------------------------------------------------------- tree traffic
class TreeWriter:
    """An in-process SharedTree replica (EditManager + forest with the
    optimistic local branch) whose channel outbox is minted into the same
    UnsequencedMessage stream a wire client sends (the
    testing.chaos.ChaosTreeWriter idiom, without the socket)."""

    def __init__(self, client_id: str) -> None:
        from fluidframework_tpu.dds.tree.shared_tree import SharedTreeChannel
        from fluidframework_tpu.protocol.channel import ChannelDeltaConnection

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
        from fluidframework_tpu.protocol.channel import (
            ChannelMessage,
            MessageCollection,
            MessageEnvelope,
        )
        from fluidframework_tpu.protocol.messages import MessageType

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
        from fluidframework_tpu.protocol.messages import (
            MessageType,
            UnsequencedMessage,
        )

        out, self._staged = self._staged, []
        msgs = []
        for contents in out:
            self._client_seq += 1
            msgs.append(UnsequencedMessage(
                client_id=self.client_id, client_seq=self._client_seq,
                ref_seq=self.last_seq, type=MessageType.OP, contents=contents,
            ))
        return msgs

    def root_json(self) -> list:
        return [n.to_json() for n in self.tree.forest.root_field]


def _tree_edit(rng: random.Random, w: TreeWriter) -> None:
    from fluidframework_tpu.dds.tree.changeset import (
        make_insert,
        make_remove,
        make_set_value,
    )
    from fluidframework_tpu.dds.tree.schema import leaf

    t = w.tree
    n = len(t.forest.root_field)
    r = rng.random()
    if n < 4 or r < 0.45:
        t.submit_change(make_insert(
            [], "", rng.randint(0, n), [leaf(rng.randrange(1000))]
        ))
    elif r < 0.65:
        # Nested edit: a child under a root node's "sub" field.
        t.submit_change(make_insert(
            [("", rng.randrange(n))], "sub", 0, [leaf(rng.randrange(1000))]
        ))
    elif r < 0.85:
        t.submit_change(
            make_set_value([("", rng.randrange(n))], rng.randrange(1000))
        )
    else:
        t.submit_change(make_remove([], "", rng.randrange(n), 1))


def tree_leg(size: dict, args, env: dict, workdir: str, device: dict,
             deadline: float) -> dict:
    from fluidframework_tpu.dds.tree.changeset import make_move
    from fluidframework_tpu.loadgen.coordinator import oracle_tree
    from fluidframework_tpu.protocol.messages import MessageType, Nack
    from fluidframework_tpu.server.netserver import NetworkServer

    rng = random.Random(args.seed + 1)
    n_docs = size["tree_docs"]
    doc_ids = [f"t{i}" for i in range(n_docs)]
    busy = doc_ids[:4]
    srv = NetworkServer().start()
    writers: dict[str, list[TreeWriter]] = {}
    child = None
    ops = 0

    def flush(doc_id: str) -> None:
        nonlocal ops
        with srv.lock:
            doc = srv.service.document(doc_id)
            for w in writers[doc_id]:
                for m in w.take_outbox():
                    res = doc.submit(m)
                    check(not isinstance(res, Nack), f"{doc_id}: nack {res}")
                    ops += 1
            doc.process_all()

    def rounds(n: int) -> None:
        for d in busy:
            for _ in range(n):
                for w in writers[d]:  # concurrent: all edit, then all submit
                    for _ in range(2):
                        _tree_edit(rng, w)
                flush(d)
            # One move, from a quiet replica: a pure move in one field is
            # device work; a move REBASED over concurrent structure may
            # split and route the doc to the host (fallback_docs).
            w = writers[d][0]
            n_root = len(w.tree.forest.root_field)
            check(n_root > 1, f"{d}: nothing to move")
            w.tree.submit_change(make_move([], "", 0, 1, n_root))
            flush(d)

    try:
        with srv.lock:
            for d in busy:
                doc = srv.service.document(d)
                writers[d] = [TreeWriter(f"{d}-w{i}") for i in range(4)]
                for w in writers[d]:
                    doc.connect(w.client_id, w.process)
                doc.process_all()
        rounds(3)  # before the fleet attaches: catch-up path
        drain_file = os.path.join(workdir, "tree-drain.json")
        cmd = [
            sys.executable, "-m", "fluidframework_tpu.server.fleet_main",
            "--port", str(srv.port), "--docs", ",".join(doc_ids),
            "--family", "tree", "--drain-file", drain_file,
            "--status-every", "3600", *size["tree_flags"],
        ]
        child = Child("tree", cmd, env, workdir)
        ready = child.wait_for(lambda s: s.get("ready"), deadline, "readiness")
        setup_s = time.monotonic() - child.t_spawn
        say(f"  ready in {setup_s:.1f}s on {ready['platform']}, resident "
            f"{ready['resident_bytes_per_device']}")
        check(ready["platform"] == device["platform"],
              f"fleet runs on {ready['platform']}, probe saw {device}")
        rounds(3)  # live
        want = {}
        with srv.lock:
            logs = {d: list(srv.service.document(d).sequencer.log)
                    for d in busy}
        for d in doc_ids:
            want[d] = max((m.seq for m in logs.get(d, [])
                           if m.type == MessageType.OP), default=0)
        with open(drain_file + ".tmp", "w") as f:
            json.dump({"want": want}, f)
        os.replace(drain_file + ".tmp", drain_file)
        final = child.wait_for(lambda s: s.get("done"), deadline, "final line")
        child.finish(deadline)
        waves_s = time.monotonic() - child.t_spawn - setup_s
        for d in doc_ids:
            if d not in writers:
                check(final["trees"][d] == [], f"{d}: untouched doc has nodes")
                continue
            oracle = json.loads(json.dumps(oracle_tree(logs[d])))
            for w in writers[d]:
                check(json.loads(json.dumps(w.root_json())) == oracle,
                      f"{d}: writer {w.client_id} != oracle")
            check(final["trees"][d] == oracle, f"{d}: device tree != oracle")
        h = final["health"]
        check(final["errors"] == 0, f"errors = {final['errors']}")
        check(h["fallback_docs"] == 0, f"fallback_docs = {h['fallback_docs']}")
        check(h["device_fraction"] == 1.0,
              f"device_fraction = {h['device_fraction']}")
        check(h["ingest_plane"] == "native" and h["tree_decode_bound"],
              f"tree ingest plane {h['ingest_plane']}, bound "
              f"{h['tree_decode_bound']}")
        return {
            "docs": n_docs,
            "resident_bytes_per_device": ready["resident_bytes_per_device"],
            "setup_seconds": round(setup_s, 1),
            "waves_seconds": round(waves_s, 1),
            "compile": final["compile"],
            "edits_applied": ops,
            "docs_verified": len(writers),
            "megastep_dispatches": h["megastep_dispatches"],
            "recompiles": h["recompiles"],
        }
    finally:
        if child is not None:
            child.kill()
        srv.stop()


# ------------------------------------------------------- leg d (child, JAX)
def kernels_child(size: dict, rehearse: bool, seed: int) -> int:
    """Compile and run, once each at bench shape against its host oracle,
    the jitted programs neither fleet leg reaches.  Runs IN a child: this
    is the only function here that imports JAX."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fluidframework_tpu.utils import compile_cache

    compile_cache.enable()
    stats = compile_cache.CompileStats().install()
    devs = jax.devices()
    out: dict = {"platform": devs[0].platform}
    rng = np.random.default_rng(seed)
    k = size["kernels"]

    # -- ops/map_kernel.py: 256 setters on one 256-key map, LWW fold -----
    from fluidframework_tpu.ops import map_kernel as mpk

    K = B = 256
    S = k["map_rounds"]
    kinds = rng.integers(1, 4, size=(S, B)).astype(np.int32)
    kinds[kinds == 3] = np.where(rng.random((kinds == 3).sum()) < 0.02, 3, 1)
    keys = rng.integers(0, K, size=(S, B)).astype(np.int32)
    vals = rng.integers(0, 1 << 20, size=(S, B)).astype(np.int32)
    seqs = np.arange(S * B, dtype=np.int32).reshape(S, B) + 1

    def run_map(state, kinds, keys, vals, seqs):
        def body(s, xs):
            return mpk.apply_batch(s, *xs), None

        return jax.lax.scan(body, state, (kinds, keys, vals, seqs))[0]

    state = jax.jit(run_map)(
        mpk.init_state(K), *map(jnp.asarray, (kinds, keys, vals, seqs))
    )
    want: dict[int, int] = {}
    for s in range(S):
        for b in range(B):
            if kinds[s, b] == mpk.MapOpKind.SET:
                want[int(keys[s, b])] = int(vals[s, b])
            elif kinds[s, b] == mpk.MapOpKind.DELETE:
                want.pop(int(keys[s, b]), None)
            else:
                want.clear()
    check(mpk.host_items(state) == want, "map kernel != LWW dict fold")
    out["map"] = {"ops": S * B, "keys_present": len(want)}

    # -- ops/matrix_kernel.py: 256 x 256 state, SharedMatrix oracle ------
    from fluidframework_tpu.dds.shared_matrix import SharedMatrix
    from fluidframework_tpu.ops import matrix_kernel as mxk
    from fluidframework_tpu.server.local_service import LocalDocument

    prng = random.Random(seed)
    doc = LocalDocument("m")
    ms = [SharedMatrix(client_id=f"c{i}") for i in range(4)]
    for m in ms:
        doc.connect(m.client_id, m.process)
    doc.process_all()

    def pump() -> None:
        for m in ms:
            for msg in m.take_outbox():
                doc.submit(msg)
        doc.process_all()

    side = k["matrix_side"]
    ms[0].insert_rows(0, side)
    ms[0].insert_cols(0, side)
    pump()
    for _round in range(12):
        for m in ms:  # concurrent: every writer edits before any submit
            for _ in range(16):
                m.set_cell(prng.randrange(m.row_count),
                           prng.randrange(m.col_count),
                           prng.randint(1, 1 << 20))
        if _round % 4 == 1:
            ms[1].insert_rows(prng.randrange(ms[1].row_count), 1)
            ms[2].remove_cols(prng.randrange(ms[2].col_count), 1)
        pump()
    quorum: dict[str, int] = {}
    rows = []
    kindmap = {"insertRows": mxk.MatrixOpKind.INSERT_ROWS,
               "insertCols": mxk.MatrixOpKind.INSERT_COLS,
               "removeRows": mxk.MatrixOpKind.REMOVE_ROWS,
               "removeCols": mxk.MatrixOpKind.REMOVE_COLS}
    for msg in doc.sequencer.log:
        if msg.type == "join":
            quorum[msg.contents["clientId"]] = msg.contents["short"]
        elif msg.type == "op":
            c, client = msg.contents, quorum[msg.client_id]
            if c["type"] == "set":
                rows.append([mxk.MatrixOpKind.SET_CELL, msg.seq, client,
                             msg.ref_seq, c["row"], c["col"], int(c["value"]),
                             1 if c.get("fwwMode") else 0])
            else:
                rows.append([kindmap[c["type"]], msg.seq, client,
                             msg.ref_seq, c["pos"], c["count"], 0, 0])
    Bm = 64
    pad = -len(rows) % Bm
    ops = np.array(rows + [[0] * mxk.MATRIX_OP_FIELDS] * pad, np.int32)

    def run_matrix(state, all_ops):
        return jax.lax.scan(
            lambda s, o: (mxk.apply_ops(s, o), None), state, all_ops
        )[0]

    mstate = jax.jit(run_matrix)(
        mxk.init_state(max_rows=256, max_cols=256, max_segments=128),
        jnp.asarray(ops.reshape(-1, Bm, mxk.MATRIX_OP_FIELDS)),
    )
    check(int(mstate.error) == 0, f"matrix kernel error {int(mstate.error)}")
    check(mxk.to_grid(mstate) == ms[0].to_grid(),
          "matrix kernel grid != SharedMatrix oracle")
    out["matrix"] = {"ops": len(rows), "grid": [len(ms[0].to_grid()),
                                                len(ms[0].to_grid()[0])]}

    # -- ops/tree_kernel.py rebase_window_batched: [windows x 8] ---------
    import bench

    _speedup, identity = bench._rebase_kernel_microbench(
        rng, n_windows=k["rebase_windows"], window=8
    )
    check(identity, "rebase_window kernel fold != pooled host fold")
    out["rebase_window"] = {"windows": k["rebase_windows"], "depth": 8}

    # -- ops/pallas_kernels.py: the Mosaic-compiled position resolve -----
    from fluidframework_tpu.ops import pallas_kernels as pk

    n_seg = k["pallas_segments"]
    lens = rng.integers(0, 9, size=n_seg).astype(np.int32)
    pos = rng.integers(-4, int(lens.sum()) + 4, size=256).astype(np.int32)
    ref = pk.resolve_positions_reference(jnp.asarray(lens), jnp.asarray(pos))
    # interpret=True exists for the CPU rehearsal only; on the chip this
    # is the compiled kernel or nothing.
    got = pk.resolve_positions_pallas(
        jnp.asarray(lens), jnp.asarray(pos), interpret=rehearse
    )
    for name, a, b in zip(("index", "offset", "hit"), ref, got):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"pallas resolve {name} != reference")
    out["pallas_resolve"] = {"queries": 256, "segments": n_seg,
                             "interpret": rehearse}
    out["compile"] = stats.snapshot()
    print(json.dumps(out), flush=True)
    return 0


def kernels_leg(size_name: str, args, env: dict, workdir: str,
                device: dict, deadline: float) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", "kernels",
           "--seed", str(args.seed)]
    if size_name == "rehearsal":
        cmd.append("--rehearse-cpu")
    child = Child("kernels", cmd, env, workdir)
    try:
        out = child.wait_for(lambda s: "pallas_resolve" in s, deadline,
                             "the kernels summary")
        child.finish(deadline)
        check(out["platform"] == device["platform"],
              f"kernels ran on {out['platform']}, probe saw {device}")
        out["seconds"] = round(time.monotonic() - child.t_spawn, 1)
        return out
    finally:
        child.kill()


# ----------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny CPU rehearsal of every leg (says platform: "
                        "cpu; never counts as a pass of the smoke)")
    p.add_argument("--mesh", type=int, default=0,
                   help="serve the string legs on an N-device docs mesh")
    p.add_argument("--legs", default=",".join(LEGS),
                   help="comma-separated subset of a,b,c,d")
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--child", choices=("kernels",), help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    size_name = "rehearsal" if args.rehearse_cpu else "chip"
    size = dict(SIZES[size_name])
    if size_name == "chip" and args.mesh >= 4:
        size["string_docs"] = CHIP_DOCS_WANTED
    if args.child == "kernels":
        return kernels_child(size, args.rehearse_cpu, args.seed)

    # Fail here, before any output, where the repo is not beside this file.
    import fluidframework_tpu.loadgen.coordinator  # noqa: F401
    import fluidframework_tpu.server.netserver  # noqa: F401

    legs = [x for x in args.legs.split(",") if x]
    if not legs or any(x not in LEGS for x in legs):
        p.error(f"--legs wants a subset of {','.join(LEGS)}")
    env = dict(os.environ)
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        if args.mesh:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.mesh}"
            )
    t0 = time.monotonic()
    device = probe_device(env)
    if args.rehearse_cpu:
        if device["platform"] != "cpu":
            print(f"chip_smoke: --rehearse-cpu, but JAX reports {device}",
                  file=sys.stderr)
            return 1
    elif device["platform"] == "cpu":
        print("chip_smoke: JAX found no accelerator (platform "
              f"{device['platform']!r}, {device['count']} x "
              f"{device['kind']}); this check does not run on the CPU "
              "(--rehearse-cpu is the explicit tiny rehearsal)",
              file=sys.stderr)
        return 1
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"count: {device['count']}  size: {size_name}")

    # One firehose socket per document on each side of the front.
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    need = size["string_docs"] + 512
    if soft < need:
        check(hard >= need, f"RLIMIT_NOFILE {hard} < {need} sockets")
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))

    reduced = []
    if size_name == "chip" and size["string_docs"] < CHIP_DOCS_WANTED:
        reduced.append({
            "what": "string documents", "from": CHIP_DOCS_WANTED,
            "to": size["string_docs"],
            "cause": "HBM: XLA's temporaries for the fleet-step program "
                     "(~1.35 MiB per document beside 0.59 MiB of state) "
                     "exhaust 15.75 GiB at compile time above ~7,800 "
                     "documents; shapes are not cut",
        })
    results: dict = {}
    failed = None
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        for leg in legs:
            say(f"leg {leg}  (t+{time.monotonic() - t0:.0f}s)")
            deadline = min(time.monotonic() + LEG_DEADLINE_S,
                           t0 + RUN_DEADLINE_S)
            try:
                if leg in ("a", "b"):
                    results[leg] = string_leg(
                        leg, size, args, env, workdir, device, deadline
                    )
                elif leg == "c":
                    results[leg] = tree_leg(
                        size, args, env, workdir, device, deadline
                    )
                else:
                    results[leg] = kernels_leg(
                        size_name, args, env, workdir, device, deadline
                    )
            except SmokeFailure as e:
                failed = f"leg {leg}: {e}"
                break
            say(f"  ok: {json.dumps(results[leg])}")
    if failed is None and "a" in results and "b" in results:
        a, b = results["a"], results["b"]
        # The warm leg must have FOUND the cache: nothing new written, and
        # every program the cold leg compiled served from disk.
        if b["cache_files_added"] or (
            b["compile"]["cache_hits"] < b["compile"]["requests"]
        ):
            failed = (f"leg b did not find the compile cache: "
                      f"{b['cache_files_added']} new files, "
                      f"{b['compile']}")
    assert "jax" not in sys.modules, "the smoke's parent imported JAX"
    # The verdict is the LAST line of stdout and holds exactly these two
    # keys; everything else the run learned is the report line above it.
    verdict = verdict_line(
        failed is None and not args.rehearse_cpu and legs == list(LEGS),
        device,
    )
    report = {
        "size": size_name,
        "legs": results,
        "reduced": reduced,
        "seconds": round(time.monotonic() - t0, 1),
        "failed": failed,
    }
    if args.rehearse_cpu:
        report["rehearsal_passed"] = failed is None
    elif legs != list(LEGS):
        report["partial"] = legs
        report["partial_passed"] = failed is None
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    with contextlib.suppress(OSError):
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump({**verdict, **report}, f, indent=2)
    if failed is not None:
        print(f"chip_smoke: FAILED — {failed}", file=sys.stderr)
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0 if failed is None else 1


if __name__ == "__main__":
    sys.exit(main())
