"""fftpu-check static-analysis suite tests.

Three tiers:

1. Per-pass fixture tests — a known-bad snippet fires the rule, its
   known-good twin stays silent (all eleven passes).
2. Baseline round-trip — add / suppress / expire, rationale enforcement.
3. Self-hosting gates — ``test_package_is_clean`` runs the whole suite on
   the real package (tier-1: every future PR is checked), and seeded
   violations on a copy of the real tree make the CLI exit nonzero with
   the right rule id.

Everything is pure AST — no JAX import, so this file runs in seconds even
on the 2-core CI box.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from fluidframework_tpu.analysis import cli as check_cli
from fluidframework_tpu.analysis.core import Baseline, load_package
from fluidframework_tpu.analysis import (
    blocking, determinism, donation, jit_safety, layer_check,
    lock_consistency, lock_order, markchurn, mesh_safety, swallowed, threads,
)

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "fluidframework_tpu"

FIXTURE_LAYERS = {
    "layers": [
        {"name": "low", "packages": ["low"]},
        {"name": "high", "packages": ["high"]},
    ],
    "determinism_scope": ["fixturepkg/low/"],
}


def make_pkg(tmp_path: Path, files: dict) -> Path:
    """Write a throwaway package tree; returns its directory."""
    pkg = tmp_path / "fixturepkg"
    for rel, body in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(body)
    for d in {p.parent for p in pkg.rglob("*.py")} | {pkg}:
        init = d / "__init__.py"
        if not init.exists():
            init.write_text("")
    (pkg / "analysis").mkdir(exist_ok=True)
    (pkg / "analysis" / "layers.json").write_text(json.dumps(FIXTURE_LAYERS))
    return pkg


def rules_of(findings) -> list:
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Pass 1: layer-check
# ---------------------------------------------------------------------------

def test_layer_check_flags_upward_import(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": "from ..high import svc\n",
        "high/svc.py": "X = 1\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert [f.rule for f in found] == ["layer-upward-import"]
    assert found[0].file == "fixturepkg/low/util.py"
    assert found[0].line == 1
    assert "fixturepkg.high.svc" in found[0].detail


def test_layer_check_good_twin_silent(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": "X = 1\n",
        "high/svc.py": "from ..low import util\nfrom ..low.util import X\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert found == []


def test_layer_check_type_checking_imports_exempt(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": (
            "from typing import TYPE_CHECKING\n"
            "if TYPE_CHECKING:\n"
            "    from ..high import svc\n"
        ),
        "high/svc.py": "X = 1\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert found == []


def test_layer_check_inverted_type_checking_guard_not_exempt(tmp_path):
    """``if not TYPE_CHECKING:`` bodies RUN — the exemption only covers the
    exact positive guard."""
    pkg = make_pkg(tmp_path, {
        "low/util.py": (
            "from typing import TYPE_CHECKING\n"
            "if not TYPE_CHECKING:\n"
            "    from ..high import svc\n"
        ),
        "high/svc.py": "X = 1\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert [f.rule for f in found] == ["layer-upward-import"]


def test_layer_check_lazy_function_local_import_still_counts(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": "def f():\n    from ..high import svc\n    return svc\n",
        "high/svc.py": "X = 1\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert [f.rule for f in found] == ["layer-upward-import"]


def test_layer_check_undeclared_subpackage(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": "X = 1\n",
        "rogue/new_thing.py": "Y = 2\n",
    })
    found = layer_check.run(load_package(pkg),
                            layer_check.load_layers(pkg / "analysis/layers.json"))
    assert [f.rule for f in found] == ["layer-undeclared-package"]
    assert "rogue" in found[0].message


# ---------------------------------------------------------------------------
# Pass 2: jit-safety
# ---------------------------------------------------------------------------

def test_jit_branch_on_tracer_fires_and_shape_branch_does_not(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def bad(x):\n"
            "    if x > 0:\n"            # traced -> finding
            "        return x\n"
            "    return -x\n"
            "@jax.jit\n"
            "def good(x):\n"
            "    if x.shape[0] > 2:\n"   # static metadata -> silent
            "        return x * 2\n"
            "    return x\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-branch-on-tracer"]
    assert found[0].line == 5
    assert "bad" in found[0].detail


def test_jit_taint_propagates_through_call_chain(tmp_path):
    # Entry wraps f via functools.partial(jax.jit, ...); f calls helper g;
    # g branches on the traced argument -> flagged inside g.
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import functools\n"
            "import jax\n"
            "def g(v):\n"
            "    while v < 3:\n"
            "        v = v + 1\n"
            "    return v\n"
            "def f(state, n):\n"
            "    return g(state) + n\n"
            "prog = functools.partial(jax.jit, donate_argnums=(0,))(f)\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-branch-on-tracer"]
    assert found[0].line == 4
    assert "g" in found[0].detail


def test_jit_isinstance_narrowing_suppresses_static_arm(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "@jax.jit\n"
            "def dual(x, flag):\n"
            "    if isinstance(flag, bool):\n"
            "        y = x * 2 if flag else x\n"   # static arm: fine
            "        return y\n"
            "    return jax.lax.cond(flag, lambda v: v * 2, lambda v: v, x)\n"
        ),
    })
    assert jit_safety.run(load_package(pkg)) == []


def test_jit_static_comprehension_branch_is_silent(tmp_path):
    """A comprehension over static data is branchable; one over traced data
    taints its result."""
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "@jax.jit\n"
            "def good(x):\n"
            "    ks = [i * 2 for i in range(4)]\n"
            "    if ks:\n"
            "        return x\n"
            "    return x\n"
        ),
    })
    assert jit_safety.run(load_package(pkg)) == []
    pkg2 = make_pkg(tmp_path / "b", {
        "low/kern.py": (
            "import jax\n"
            "@jax.jit\n"
            "def bad(xs):\n"
            "    ys = [v + 1 for v in xs]\n"
            "    if ys[0]:\n"
            "        return xs\n"
            "    return xs\n"
        ),
    })
    assert [f.rule for f in jit_safety.run(load_package(pkg2))] == \
        ["jit-branch-on-tracer"]


def test_jit_bound_method_entry(tmp_path):
    """``self._prog = jax.jit(self._step, ...)`` registers the method as a
    jit entry — hazards inside it are not silently dropped."""
    pkg = make_pkg(tmp_path, {
        "low/eng.py": (
            "import jax\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self._prog = jax.jit(self._step, donate_argnums=(0,))\n"
            "    def _step(self, state):\n"
            "        if state > 0:\n"
            "            return state\n"
            "        return -state\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-branch-on-tracer"]
    assert "_step" in found[0].detail


def test_jit_np_on_tracer(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "import numpy as np\n"
            "import jax.numpy as jnp\n"
            "@jax.jit\n"
            "def bad(x):\n"
            "    return np.cumsum(x)\n"
            "@jax.jit\n"
            "def good(x):\n"
            "    scale = np.float32(4.0)\n"   # np on a constant: fine
            "    return jnp.cumsum(x) * scale\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-np-on-tracer"]
    assert found[0].line == 6


def test_jit_host_sync(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "@jax.jit\n"
            "def bad(x):\n"
            "    return float(x) + 1\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-host-sync"]


def test_jit_unhashable_static(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/kern.py": (
            "import jax\n"
            "def f(x, opts):\n"
            "    return x\n"
            "prog = jax.jit(f, static_argnames=('opts',))\n"
            "def caller(x):\n"
            "    bad = prog(x, opts=['a', 'b'])\n"
            "    good = prog(x, opts=('a', 'b'))\n"
            "    return bad, good\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-unhashable-static"]
    assert found[0].line == 6


def test_host_sync_loop_and_bulk_twin(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/host.py": (
            "import numpy as np\n"
            "def bad(cols, n):\n"
            "    out = []\n"
            "    for i in range(n):\n"
            "        out.append([c[i].item() for c in cols])\n"
            "    return out\n"
            "def good(cols, n):\n"
            "    lists = [np.asarray(c).tolist() for c in cols]\n"
            "    return [[c[i] for c in lists] for i in range(n)]\n"
        ),
    })
    found = jit_safety.run(load_package(pkg))
    assert [f.rule for f in found] == ["jit-host-sync-loop"]
    assert found[0].line == 5


# ---------------------------------------------------------------------------
# Pass 3: donation
# ---------------------------------------------------------------------------

DONATE_HEADER = (
    "import functools\n"
    "import jax\n"
    "def step(state, ops):\n"
    "    return state\n"
    "prog = functools.partial(jax.jit, donate_argnums=(0,))(step)\n"
)


def test_donation_use_after_dispatch(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/eng.py": DONATE_HEADER + (
            "def bad(state, ops):\n"
            "    out = prog(state, ops)\n"
            "    return state, out\n"       # state is donated: finding
            "def good(state, ops):\n"
            "    state = prog(state, ops)\n"  # rebind kills the donation
            "    return state\n"
        ),
    })
    found = donation.run(load_package(pkg))
    assert [f.rule for f in found] == ["donate-use-after-dispatch"]
    assert "bad" in found[0].detail and "`state`" in found[0].message


def test_donation_loop_carried(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/eng.py": DONATE_HEADER + (
            "def bad(state, batches):\n"
            "    for ops in batches:\n"
            "        out = prog(state, ops)\n"  # 2nd iter uses donated state
            "    return out\n"
            "def good(state, batches):\n"
            "    for ops in batches:\n"
            "        state = prog(state, ops)\n"
            "    return state\n"
        ),
    })
    found = donation.run(load_package(pkg))
    assert [f.rule for f in found] == ["donate-use-after-dispatch"]
    assert "bad" in found[0].detail


def test_donation_self_attribute_program(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/eng.py": (
            "import jax\n"
            "class Engine:\n"
            "    def __init__(self, fn, mesh):\n"
            "        self._prog = mesh_fleet_program(fn, mesh)\n"
            "    def bad_step(self, ops):\n"
            "        new = self._prog(self._state, ops)\n"
            "        n = self._state.nseg\n"     # read before rebind
            "        self._state = new\n"
            "        return n\n"
            "    def good_step(self, ops):\n"
            "        self._state = self._prog(self._state, ops)\n"
            "        return self._state.nseg\n"
            "def mesh_fleet_program(fn, mesh):\n"
            "    return fn\n"
        ),
    })
    found = donation.run(load_package(pkg))
    assert [f.rule for f in found] == ["donate-use-after-dispatch"]
    assert "bad_step" in found[0].detail


def test_donation_call_inside_if_test(tmp_path):
    """The if-test evaluates before its arms: a donating call there poisons
    uses in either branch body."""
    pkg = make_pkg(tmp_path, {
        "low/eng.py": DONATE_HEADER + (
            "def bad(state, ops):\n"
            "    if prog(state, ops) is None:\n"
            "        return state.nseg\n"
            "    return 0\n"
        ),
    })
    found = donation.run(load_package(pkg))
    assert [f.rule for f in found] == ["donate-use-after-dispatch"]
    assert "bad" in found[0].detail


# ---------------------------------------------------------------------------
# Pass 4: determinism
# ---------------------------------------------------------------------------

def test_determinism_rules_fire_in_scope_only(tmp_path):
    fold_bad = (
        "import time, random\n"
        "def fold(self):\n"
        "    acc = []\n"
        "    pending = set()\n"
        "    for d in pending:\n"            # det-set-iteration
        "        acc.append(d)\n"
        "    acc.sort(key=lambda x: id(x))\n"  # det-id-ordering
        "    stamp = time.time()\n"            # det-wallclock
        "    salt = random.random()\n"         # det-random
        "    h = hash('doc')\n"                # det-hash-builtin
        "    return acc, stamp, salt, h\n"
    )
    pkg = make_pkg(tmp_path, {
        "low/fold.py": fold_bad,
        "high/serving.py": fold_bad,  # out of scope: silent
    })
    scope = ["fixturepkg/low/"]
    found = determinism.run(load_package(pkg), scope)
    assert rules_of(found) == [
        "det-hash-builtin", "det-id-ordering", "det-random",
        "det-set-iteration", "det-wallclock",
    ]
    assert all(f.file == "fixturepkg/low/fold.py" for f in found)


def test_determinism_sorted_and_minmax_are_silent(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/fold.py": (
            "def fold(docs, refs):\n"
            "    seen = set(docs) | set(refs)\n"
            "    lo = min(seen)\n"
            "    for d in sorted(seen):\n"
            "        lo = d\n"
            "    return [x for x in sorted(seen)], lo\n"
        ),
    })
    assert determinism.run(load_package(pkg), ["fixturepkg/low/"]) == []


def test_determinism_rebind_to_sorted_is_silent(tmp_path):
    """The fix the rule's own hint recommends must not itself be flagged:
    rebinding a set-typed local to sorted(...) kills its set-typedness."""
    pkg = make_pkg(tmp_path, {
        "low/fold.py": (
            "def f(items):\n"
            "    docs = set(items)\n"
            "    docs = sorted(docs)\n"
            "    out = []\n"
            "    for d in docs:\n"
            "        out.append(d)\n"
            "    return out\n"
        ),
    })
    assert determinism.run(load_package(pkg), ["fixturepkg/low/"]) == []


def test_determinism_per_use_flow(tmp_path):
    """Verdicts are per-use: iterating the set BEFORE a later rebind still
    fires; a loop over a plain parameter isn't retro-tainted by a later
    set assignment to the same name."""
    pkg = make_pkg(tmp_path, {
        "low/a.py": (
            "def f(xs):\n"
            "    s = set(xs)\n"
            "    out = []\n"
            "    for d in s:\n"         # real hazard: before the rebind
            "        out.append(d)\n"
            "    s = sorted(s)\n"
            "    return s\n"
        ),
        "low/b.py": (
            "def g(s):\n"
            "    out = []\n"
            "    for x in s:\n"          # plain parameter: fine
            "        out.append(x)\n"
            "    s = set(out)\n"
            "    return sorted(s)\n"
        ),
    })
    found = determinism.run(load_package(pkg), ["fixturepkg/low/"])
    assert [(f.file, f.rule) for f in found] == \
        [("fixturepkg/low/a.py", "det-set-iteration")]


def test_determinism_set_typed_attribute(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/fold.py": (
            "class Scribe:\n"
            "    def __init__(self):\n"
            "        self.docs: set[str] = set()\n"
            "    def fold(self):\n"
            "        return list(self.docs)\n"   # materializes in hash order
        ),
    })
    found = determinism.run(load_package(pkg), ["fixturepkg/low/"])
    assert [f.rule for f in found] == ["det-set-iteration"]


# ---------------------------------------------------------------------------
# Pass 5: threads
# ---------------------------------------------------------------------------

THREAD_BAD = (
    "import threading\n"
    "class Worker:\n"
    "    def __init__(self):\n"
    "        self.count = 0\n"
    "        self._lock = threading.Lock()\n"
    "        self._thread = threading.Thread(target=self._run, daemon=True)\n"
    "    def _run(self):\n"
    "        while True:\n"
    "            self.count += 1\n"
    "    def snapshot(self):\n"
    "        return self.count\n"
)

THREAD_GOOD = THREAD_BAD.replace(
    "        while True:\n"
    "            self.count += 1\n",
    "        while True:\n"
    "            with self._lock:\n"
    "                self.count += 1\n",
)


def test_threads_unlocked_write_fires_and_locked_twin_silent(tmp_path):
    pkg_bad = make_pkg(tmp_path / "bad", {"low/w.py": THREAD_BAD})
    found = threads.run(load_package(pkg_bad))
    assert [f.rule for f in found] == ["thread-unlocked-write"]
    assert ".count" in found[0].message and "_run" in found[0].detail

    pkg_good = make_pkg(tmp_path / "good", {"low/w.py": THREAD_GOOD})
    assert threads.run(load_package(pkg_good)) == []


def test_threads_lock_inherited_through_call_edge(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/w.py": (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self.jobs = 0\n"
            "        self._lock = threading.Lock()\n"
            "        self._thread = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n"       # callee under the lock
            "    def _bump(self):\n"
            "        self.jobs += 1\n"
            "    def read(self):\n"
            "        return self.jobs\n"
        ),
    })
    assert threads.run(load_package(pkg)) == []


def test_threads_other_class_same_attr_name_is_not_a_race(tmp_path):
    """A thread-side ``self.count`` write in Writer must not match another
    class's own ``self.count`` — different objects, no shared state."""
    pkg = make_pkg(tmp_path, {
        "low/w.py": (
            "import threading\n"
            "class Writer:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self.count += 1\n"
            "class Unrelated:\n"
            "    def __init__(self):\n"
            "        self.count = 5\n"
            "    def peek(self):\n"
            "        return self.count\n"
        ),
    })
    assert threads.run(load_package(pkg)) == []


def test_threads_module_function_target(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/w.py": (
            "import threading\n"
            "def _drain(shard):\n"
            "    shard.offset = 1\n"
            "def start(shard):\n"
            "    threading.Thread(target=_drain, args=(shard,)).start()\n"
            "def peek(shard):\n"
            "    return shard.offset\n"
        ),
    })
    found = threads.run(load_package(pkg))
    assert [f.rule for f in found] == ["thread-unlocked-write"]
    assert ".offset" in found[0].message


EXECUTOR_BAD = (
    "import threading\n"
    "from concurrent.futures import ThreadPoolExecutor\n"
    "class Restorer:\n"
    "    def __init__(self):\n"
    "        self.loaded = 0\n"
    "        self._lock = threading.Lock()\n"
    "    def _load_one(self, doc):\n"
    "        self.loaded += 1\n"
    "    def restore(self, docs):\n"
    "        with ThreadPoolExecutor(max_workers=4) as ex:\n"
    "            for d in docs:\n"
    "                ex.submit(self._load_one, d)\n"
    "    def stats(self):\n"
    "        return self.loaded\n"
)

EXECUTOR_GOOD = EXECUTOR_BAD.replace(
    "    def _load_one(self, doc):\n"
    "        self.loaded += 1\n",
    "    def _load_one(self, doc):\n"
    "        with self._lock:\n"
    "            self.loaded += 1\n",
)


def test_threads_executor_submit_is_a_thread_entry(tmp_path):
    """ISSUE 12 coverage extension: a ThreadPoolExecutor worker body is a
    thread entry (the parallel-restore fan-out shape) — an unlocked write
    it makes to state the host path reads must fire, and the locked twin
    must stay silent."""
    pkg_bad = make_pkg(tmp_path / "bad", {"low/r.py": EXECUTOR_BAD})
    found = threads.run(load_package(pkg_bad))
    assert [f.rule for f in found] == ["thread-unlocked-write"]
    assert ".loaded" in found[0].message and "_load_one" in found[0].detail

    pkg_good = make_pkg(tmp_path / "good", {"low/r.py": EXECUTOR_GOOD})
    assert threads.run(load_package(pkg_good)) == []


def test_threads_executor_map_and_with_binding(tmp_path):
    """``ex.map(fn, ...)`` over a with-bound executor also enters fn on
    worker threads (CheckpointStore.load_many's exact shape)."""
    pkg = make_pkg(tmp_path, {
        "low/r.py": (
            "from concurrent.futures import ThreadPoolExecutor\n"
            "def _read(store):\n"
            "    store.hits = store.hits + 1\n"
            "def load_all(stores):\n"
            "    with ThreadPoolExecutor(max_workers=2) as pool:\n"
            "        return list(pool.map(_read, stores))\n"
            "def peek(store):\n"
            "    return store.hits\n"
        ),
    })
    found = threads.run(load_package(pkg))
    assert [f.rule for f in found] == ["thread-unlocked-write"]
    assert ".hits" in found[0].message


def test_threads_timer_function_is_a_thread_entry(tmp_path):
    """``threading.Timer(t, fn)`` runs fn on the timer thread — the
    lease-heartbeat/background-writer shape; positional and keyword
    forms both count, and the locked twin stays silent."""
    bad = (
        "import threading\n"
        "class Beat:\n"
        "    def __init__(self):\n"
        "        self.renewals = 0\n"
        "        self._lock = threading.Lock()\n"
        "        threading.Timer(1.0, self._renew).start()\n"
        "    def _renew(self):\n"
        "        self.renewals += 1\n"
        "    def stats(self):\n"
        "        return self.renewals\n"
    )
    pkg_bad = make_pkg(tmp_path / "bad", {"low/b.py": bad})
    found = threads.run(load_package(pkg_bad))
    assert [f.rule for f in found] == ["thread-unlocked-write"]
    assert ".renewals" in found[0].message

    good = bad.replace(
        "    def _renew(self):\n"
        "        self.renewals += 1\n",
        "    def _renew(self):\n"
        "        with self._lock:\n"
        "            self.renewals += 1\n",
    )
    pkg_good = make_pkg(tmp_path / "good", {"low/b.py": good})
    assert threads.run(load_package(pkg_good)) == []


# ---------------------------------------------------------------------------
# Pass 6: swallowed-exception
# ---------------------------------------------------------------------------

SWALLOWED_LAYERS = {
    "layers": [
        {"name": "state", "packages": ["low"]},
        {"name": "host", "packages": ["mid"]},
        {"name": "service", "packages": ["high"]},
    ],
    "determinism_scope": [],
}


def _swallowed_pkg(tmp_path, files):
    pkg = make_pkg(tmp_path, files)
    (pkg / "analysis" / "layers.json").write_text(json.dumps(SWALLOWED_LAYERS))
    return pkg


def test_swallowed_exception_fires_in_host_and_service_layers(tmp_path):
    body = (
        "def f(g):\n"
        "    try:\n"
        "        g()\n"
        "    except (OSError, ValueError):\n"
        "        pass\n"
    )
    pkg = _swallowed_pkg(tmp_path, {
        "low/util.py": body,   # state layer: out of scope by design
        "mid/drv.py": body,    # host layer: flagged
        "high/svc.py": body,   # service layer: flagged
    })
    found = swallowed.run(
        load_package(pkg),
        layer_check.load_layers(pkg / "analysis/layers.json"),
    )
    assert [f.rule for f in found] == ["swallowed-exception"] * 2
    assert sorted(f.file for f in found) == [
        "fixturepkg/high/svc.py", "fixturepkg/mid/drv.py",
    ]
    assert all("except (OSError, ValueError): pass in f" == f.detail
               for f in found)
    assert all(f.line == 4 for f in found)


def test_swallowed_exception_good_twins_silent(tmp_path):
    pkg = _swallowed_pkg(tmp_path, {
        # Counting, re-raising, returning, suppress(): all observable or
        # explicitly-intentional — none is a silent swallow.
        "high/svc.py": (
            "import contextlib\n"
            "def counted(g, c):\n"
            "    try:\n"
            "        g()\n"
            "    except OSError:\n"
            "        c.errors += 1\n"
            "def reraised(g):\n"
            "    try:\n"
            "        g()\n"
            "    except OSError:\n"
            "        raise RuntimeError('boom')\n"
            "def returned(g):\n"
            "    try:\n"
            "        g()\n"
            "    except OSError:\n"
            "        return None\n"
            "def suppressed(g):\n"
            "    with contextlib.suppress(OSError):\n"
            "        g()\n"
        ),
    })
    found = swallowed.run(
        load_package(pkg),
        layer_check.load_layers(pkg / "analysis/layers.json"),
    )
    assert found == []


def test_swallowed_exception_bare_except_and_module_level(tmp_path):
    pkg = _swallowed_pkg(tmp_path, {
        "mid/drv.py": (
            "try:\n"
            "    import optional_thing\n"
            "except ImportError:\n"
            "    pass\n"
        ),
    })
    found = swallowed.run(
        load_package(pkg),
        layer_check.load_layers(pkg / "analysis/layers.json"),
    )
    assert [f.detail for f in found] == [
        "except ImportError: pass in <module>"
    ]


def test_swallowed_exception_explicit_scope_must_name_real_layers(tmp_path):
    """The committed layers.json pins ``swallowed_scope`` explicitly: a
    layer reshuffle that orphans a scoped name must fail loudly, never
    silently narrow the pass to nothing."""
    pkg = make_pkg(tmp_path, {"low/util.py": "X = 1\n"})
    with pytest.raises(ValueError, match="unknown layer"):
        swallowed.run(
            load_package(pkg),
            layer_check.load_layers(pkg / "analysis/layers.json"),
            scope_names=["host", "service"],
        )
    # And the real package's layers.json does pin it.
    real_cfg = json.loads((PKG / "analysis" / "layers.json").read_text())
    assert real_cfg.get("swallowed_scope") == ["host", "service"]


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------

def _one_finding_pkg(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/util.py": "from ..high import svc\n",
        "high/svc.py": "X = 1\n",
    })
    return pkg


def test_baseline_add_suppress_expire(tmp_path):
    pkg = _one_finding_pkg(tmp_path)
    result = check_cli.run_all(pkg)
    assert [f.rule for f in result["findings"]] == ["layer-upward-import"]

    # Add: suppress exactly that finding.
    f = result["findings"][0]
    baseline = pkg / "analysis" / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [{
        "rule": f.rule, "file": f.file, "detail": f.detail,
        "rationale": "fixture: vetted legacy edge",
    }]}))
    result = check_cli.run_all(pkg)
    assert result["findings"] == [] and len(result["suppressed"]) == 1
    assert result["stale_baseline"] == []

    # Expire: fix the source; the entry must surface as stale.
    (pkg / "low" / "util.py").write_text("X = 1\n")
    result = check_cli.run_all(pkg)
    assert result["findings"] == []
    assert len(result["stale_baseline"]) == 1
    assert result["stale_baseline"][0]["rule"] == "layer-upward-import"


def test_baseline_requires_rationale():
    with pytest.raises(ValueError, match="rationale"):
        Baseline([{"rule": "r", "file": "f", "detail": "d"}])


def test_baseline_matching_ignores_line_numbers(tmp_path):
    pkg = _one_finding_pkg(tmp_path)
    f = check_cli.run_all(pkg)["findings"][0]
    (pkg / "analysis" / "baseline.json").write_text(json.dumps({"suppressions": [{
        "rule": f.rule, "file": f.file, "detail": f.detail,
        "rationale": "fixture: vetted",
    }]}))
    # Shift the import down 5 lines: still suppressed.
    src = pkg / "low" / "util.py"
    src.write_text("# pad\n" * 5 + src.read_text())
    result = check_cli.run_all(pkg)
    assert result["findings"] == [] and len(result["suppressed"]) == 1


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_exit_codes_and_json(tmp_path, capsys):
    pkg = _one_finding_pkg(tmp_path)
    assert check_cli.main([str(pkg)]) == 1
    capsys.readouterr()
    assert check_cli.main([str(pkg), "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["clean"] is False
    assert out["counts"] == {"layer-upward-import": 1}
    assert out["findings"][0]["file"] == "fixturepkg/low/util.py"

    (pkg / "low" / "util.py").write_text("X = 1\n")
    assert check_cli.main([str(pkg)]) == 0
    capsys.readouterr()
    assert check_cli.main([str(pkg), "--rules", "nonsense"]) == 2
    capsys.readouterr()


def test_cli_syntax_error_is_exit_2(tmp_path, capsys):
    pkg = _one_finding_pkg(tmp_path)
    (pkg / "low" / "broken.py").write_text("def f(:\n")
    assert check_cli.main([str(pkg)]) == 2
    assert "broken.py" in capsys.readouterr().err


def test_cli_rules_subset(tmp_path, capsys):
    pkg = _one_finding_pkg(tmp_path)
    # Only non-layer passes: the upward import is out of the subset.
    assert check_cli.main([str(pkg), "--rules", "determinism,threads"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# Self-hosting gates (the real package)
# ---------------------------------------------------------------------------

def test_package_is_clean():
    """Tier-1 gate: zero unsuppressed findings on the committed tree, no
    stale baseline entries (the baseline only shrinks), every suppression
    carries a rationale (Baseline refuses otherwise)."""
    result = check_cli.run_all(PKG)
    assert result["n_modules"] > 100
    pretty = "\n".join(f.render() for f in result["findings"])
    assert not result["findings"], f"unsuppressed findings:\n{pretty}"
    assert not result["stale_baseline"], (
        f"stale baseline entries (remove them): {result['stale_baseline']}"
    )


# ---------------------------------------------------------------------------
# Pass 7: fold-mark-churn
# ---------------------------------------------------------------------------

CHURN_SCOPE = {
    "files": ["fixturepkg/fold/pool.py"],
    "classes": ["Skip", "Remove"],
    "exempt_functions": ["to_marks"],
}


def test_fold_mark_churn_fires_on_loop_and_comprehension(tmp_path):
    pkg = make_pkg(tmp_path, {
        "fold/pool.py": (
            "class Skip:\n"
            "    def __init__(self, n):\n"
            "        self.n = n\n"
            "def fold(counts):\n"
            "    out = []\n"
            "    for c in counts:\n"
            "        out.append(Skip(c))\n"
            "    return out\n"
            "def fold2(counts):\n"
            "    return [Skip(c) for c in counts]\n"
        ),
    })
    found = markchurn.run(load_package(pkg), CHURN_SCOPE)
    assert [f.rule for f in found] == ["fold-mark-churn"] * 2
    details = sorted(f.detail for f in found)
    assert details == ["Skip in fold (loop)", "Skip in fold2 (comprehension)"]


def test_fold_mark_churn_good_twins_silent(tmp_path):
    pkg = make_pkg(tmp_path, {
        "fold/pool.py": (
            "class Skip:\n"
            "    def __init__(self, n):\n"
            "        self.n = n\n"
            "class Remove:\n"
            "    def __init__(self, n):\n"
            "        self.n = n\n"
            # one-off construction outside any loop: fine
            "def head(c):\n"
            "    return Skip(c)\n"
            # the sanctioned materialization boundary, by name
            "def to_marks(counts):\n"
            "    return [Skip(c) for c in counts]\n"
            # column rows in a loop: the pooled idiom, no mark objects
            "def fold(counts):\n"
            "    rows = []\n"
            "    for c in counts:\n"
            "        rows.append((0, c, 0, 0, None))\n"
            "    return rows\n"
        ),
        # churn OUTSIDE the scoped files (the object oracle): fine
        "oracle/changeset.py": (
            "class Skip:\n"
            "    def __init__(self, n):\n"
            "        self.n = n\n"
            "def rebase(counts):\n"
            "    return [Skip(c) for c in counts]\n"
        ),
    })
    assert markchurn.run(load_package(pkg), CHURN_SCOPE) == []


def test_fold_mark_churn_disabled_without_scope(tmp_path):
    pkg = make_pkg(tmp_path, {
        "fold/pool.py": (
            "class Skip:\n"
            "    def __init__(self, n):\n"
            "        self.n = n\n"
            "def fold(counts):\n"
            "    return [Skip(c) for c in counts]\n"
        ),
    })
    assert markchurn.run(load_package(pkg), None) == []
    assert markchurn.run(load_package(pkg), {}) == []


# ---------------------------------------------------------------------------
# Pass 8: lock-order
# ---------------------------------------------------------------------------

LOCK_HEADER = (
    "import threading\n"
    "la = threading.Lock()\n"
    "lb = threading.Lock()\n"
)


def test_lock_order_cycle_via_nesting(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/locks.py": LOCK_HEADER + (
            "def f():\n"
            "    with la:\n"
            "        with lb:\n"
            "            pass\n"
            "def g():\n"
            "    with lb:\n"
            "        with la:\n"
            "            pass\n"
        ),
    })
    found = lock_order.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-order-cycle"]
    assert "la" in found[0].detail and "lb" in found[0].detail


def test_lock_order_consistent_nesting_silent(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/locks.py": LOCK_HEADER + (
            "def f():\n"
            "    with la:\n"
            "        with lb:\n"
            "            pass\n"
            "def g():\n"
            "    with la:\n"
            "        with lb:\n"
            "            pass\n"
            "def h():\n"          # release-then-take is NOT an inversion
            "    with lb:\n"
            "        pass\n"
            "    with la:\n"
            "        pass\n"
        ),
    })
    assert lock_order.run(load_package(pkg), {}) == []


def test_lock_order_multi_item_with_counts_as_nesting(tmp_path):
    """``with la, lb:`` acquires lb WHILE la is held — the single-statement
    form must produce the same la -> lb edge as the nested form (review
    regression: the edge was recorded against the pre-statement held
    set, silently dropping the AB half of a textbook AB/BA deadlock)."""
    pkg = make_pkg(tmp_path, {
        "low/locks.py": LOCK_HEADER + (
            "def f():\n"
            "    with la, lb:\n"
            "        pass\n"
            "def g():\n"
            "    with lb:\n"
            "        with la:\n"
            "            pass\n"
        ),
    })
    found = lock_order.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-order-cycle"]


def test_lock_order_cycle_through_call_edge(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/locks.py": LOCK_HEADER + (
            "def helper():\n"
            "    with lb:\n"
            "        pass\n"
            "def f():\n"
            "    with la:\n"
            "        helper()\n"      # la -> lb, one call deep
            "def other():\n"
            "    with la:\n"
            "        pass\n"
            "def g():\n"
            "    with lb:\n"
            "        other()\n"       # lb -> la: cycle
        ),
    })
    found = lock_order.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-order-cycle"]


def test_lock_order_shared_lock_unifies_across_modules(tmp_path):
    """The engines acquire ``self.ckpt_lock``; models/recovery acquires
    ``engine.ckpt_lock`` on an untyped parameter.  The shared_locks
    registry is what makes those ONE lock — without it the reversed
    nesting in another module is invisible."""
    files = {
        "low/eng.py": (
            "import threading\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self.ckpt_lock = threading.RLock()\n"
            "        self.io_lock = threading.Lock()\n"
            "    def sweep(self):\n"
            "        with self.ckpt_lock:\n"
            "            with self.io_lock:\n"
            "                pass\n"
        ),
        "low/recovery.py": (
            "def write_records(engine):\n"
            "    with engine.io_lock:\n"
            "        with engine.ckpt_lock:\n"
            "            pass\n"
        ),
    }
    pkg = make_pkg(tmp_path / "shared", files)
    found = lock_order.run(
        load_package(pkg), {"shared_locks": ["ckpt_lock", "io_lock"]}
    )
    assert [f.rule for f in found] == ["lock-order-cycle"]
    assert "ckpt_lock" in found[0].detail

    pkg2 = make_pkg(tmp_path / "unshared", files)
    assert lock_order.run(load_package(pkg2), {}) == []


def test_walk_budget_exhaustion_raises_not_false_clean(tmp_path):
    """A truncated walk must FAIL the run, never report clean on an
    unfinished analysis (review regression: the budget exhausted
    silently)."""
    from fluidframework_tpu.analysis.core import walk_lock_flow

    pkg = make_pkg(tmp_path, {
        "low/locks.py": LOCK_HEADER + (
            "def f():\n"
            "    with la:\n"
            "        g()\n"
            "def g():\n"
            "    f()\n"
        ),
    })
    # Mutual recursion under a lock converges (contexts are finite)...
    assert lock_order.run(load_package(pkg), {}) == []
    # ...but an engine starved of budget must raise, not return partial.
    with pytest.raises(RuntimeError, match="work budget"):
        walk_lock_flow(
            [(("k", i), frozenset()) for i in range(10)],
            lambda key, held: None,
            max_items=3,
        )


def test_lock_order_reentrant_self_acquire_silent(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/eng.py": (
            "import threading\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self.ckpt_lock = threading.RLock()\n"
            "    def step(self):\n"
            "        with self.ckpt_lock:\n"
            "            self.maybe_checkpoint()\n"
            "    def maybe_checkpoint(self):\n"
            "        with self.ckpt_lock:\n"   # re-entrant: fine
            "            pass\n"
        ),
    })
    assert lock_order.run(load_package(pkg), {}) == []


# ---------------------------------------------------------------------------
# Pass 9: lock-consistency
# ---------------------------------------------------------------------------

CONS_BAD = (
    "import threading\n"
    "class Counter:\n"
    "    def __init__(self):\n"
    "        self.n = 0\n"
    "        self._lock = threading.Lock()\n"
    "        self._t = threading.Thread(target=self._run)\n"
    "    def _run(self):\n"
    "        with self._lock:\n"
    "            self.n += 1\n"
    "def reset(c: Counter):\n"
    "    c.n = 0\n"                      # no lock: excludes nobody
)

CONS_GOOD = CONS_BAD.replace(
    "def reset(c: Counter):\n"
    "    c.n = 0\n",
    "def reset(c: Counter):\n"
    "    with c._lock:\n"
    "        c.n = 0\n",
)


def test_lock_consistency_unlocked_nonthread_write_fires(tmp_path):
    pkg = make_pkg(tmp_path / "bad", {"low/c.py": CONS_BAD})
    found = lock_consistency.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-inconsistent-guard"]
    assert "Counter.n" in found[0].detail and "no lock" in found[0].detail
    # The threads pass does NOT own this shape (its thread-side write IS
    # locked) — the two passes split the space, no double report.
    assert threads.run(load_package(pkg)) == []

    pkg_good = make_pkg(tmp_path / "good", {"low/c.py": CONS_GOOD})
    assert lock_consistency.run(load_package(pkg_good), {}) == []


def test_lock_consistency_two_different_locks_fire(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/c.py": (
            "import threading\n"
            "class Counter:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "        self._lock = threading.Lock()\n"
            "        self._other = threading.Lock()\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
            "    def reset(self):\n"
            "        with self._other:\n"     # disjoint lock: no exclusion
            "            self.n = 0\n"
        ),
    })
    found = lock_consistency.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-inconsistent-guard"]
    assert "Counter._lock" in found[0].message
    assert "Counter._other" in found[0].message


def test_lock_consistency_two_thread_race_not_dropped(tmp_path):
    """Locked-vs-unlocked between two THREADS has no non-thread toucher,
    so the threads pass never fires — this pass must own it (review
    regression: the unlocked thread site was excluded as 'the threads
    pass's beat' even when that pass could not fire)."""
    pkg = make_pkg(tmp_path, {
        "low/c.py": (
            "import threading\n"
            "class Pump:\n"
            "    def __init__(self):\n"
            "        self.count = 0\n"
            "        self._lock = threading.Lock()\n"
            "        threading.Thread(target=self._drain).start()\n"
            "        threading.Thread(target=self._reset).start()\n"
            "    def _drain(self):\n"
            "        with self._lock:\n"
            "            self.count += 1\n"
            "    def _reset(self):\n"
            "        self.count = 0\n"       # forgot the lock
        ),
    })
    assert threads.run(load_package(pkg)) == []
    found = lock_consistency.run(load_package(pkg), {})
    assert [f.rule for f in found] == ["lock-inconsistent-guard"]
    assert "Pump.count" in found[0].detail


def test_lock_consistency_thread_unlocked_left_to_threads_pass(tmp_path):
    """A fully-unlocked attr (thread side included) is the threads pass's
    finding; lock-consistency stays quiet rather than double-reporting."""
    pkg = make_pkg(tmp_path, {"low/w.py": THREAD_BAD})
    assert lock_consistency.run(load_package(pkg), {}) == []
    assert [f.rule for f in threads.run(load_package(pkg))] == \
        ["thread-unlocked-write"]


def test_lock_consistency_init_exempt(tmp_path):
    pkg = make_pkg(tmp_path, {"low/c.py": CONS_GOOD})
    # __init__'s unlocked self.n = 0 never counts as a site.
    assert lock_consistency.run(load_package(pkg), {}) == []


# ---------------------------------------------------------------------------
# Pass 10: blocking-under-lock
# ---------------------------------------------------------------------------

BLOCK_CFG = {
    "shared_locks": ["ckpt_lock"],
    "critical_locks": [
        {"lock": "ckpt_lock", "deny": ["fsync", "sleep"]},
    ],
}

BLOCK_BAD = (
    "import os\n"
    "import threading\n"
    "class Eng:\n"
    "    def __init__(self):\n"
    "        self.ckpt_lock = threading.RLock()\n"
    "    def save(self, fd):\n"
    "        with self.ckpt_lock:\n"
    "            os.fsync(fd)\n"
)

BLOCK_GOOD = BLOCK_BAD.replace(
    "        with self.ckpt_lock:\n"
    "            os.fsync(fd)\n",
    "        with self.ckpt_lock:\n"
    "            pass\n"
    "        os.fsync(fd)\n",       # after release: the sanctioned shape
)


def test_blocking_under_lock_fires_and_release_twin_silent(tmp_path):
    pkg = make_pkg(tmp_path / "bad", {"low/e.py": BLOCK_BAD})
    found = blocking.run(load_package(pkg), BLOCK_CFG)
    assert [f.rule for f in found] == ["blocking-under-lock"]
    assert "fsync" in found[0].detail and "ckpt_lock" in found[0].detail

    pkg_good = make_pkg(tmp_path / "good", {"low/e.py": BLOCK_GOOD})
    assert blocking.run(load_package(pkg_good), BLOCK_CFG) == []


def test_blocking_under_lock_transitive_call_edge(tmp_path):
    """The lock rides call edges — exactly how the real finding this pass
    shipped with was reachable (step -> maybe_checkpoint -> the recovery
    plane's fsync), two modules away from the ``with``."""
    pkg = make_pkg(tmp_path, {
        "low/e.py": (
            "import threading\n"
            "from .io import write_all\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self.ckpt_lock = threading.RLock()\n"
            "    def step(self):\n"
            "        with self.ckpt_lock:\n"
            "            write_all(self)\n"
        ),
        "low/io.py": (
            "import time\n"
            "def write_all(engine):\n"
            "    time.sleep(0.1)\n"
        ),
    })
    found = blocking.run(load_package(pkg), BLOCK_CFG)
    assert [f.rule for f in found] == ["blocking-under-lock"]
    assert found[0].file == "fixturepkg/low/io.py"
    assert "sleep" in found[0].detail


def test_blocking_under_lock_exempt_function(tmp_path):
    cfg = {
        "shared_locks": ["ckpt_lock"],
        "critical_locks": [
            {"lock": "ckpt_lock", "deny": ["fsync", "sleep"],
             "exempt": ["Eng.save"]},
        ],
    }
    pkg = make_pkg(tmp_path, {"low/e.py": BLOCK_BAD})
    assert blocking.run(load_package(pkg), cfg) == []


def test_blocking_under_lock_configured_package_call(tmp_path):
    """``blocking_calls`` carries the hand-knowledge static typing cannot:
    ``store.save`` fsyncs, whoever ``store`` is."""
    cfg = {
        "shared_locks": ["ckpt_lock"],
        "critical_locks": [{"lock": "ckpt_lock", "deny": ["fsync"]}],
        "blocking_calls": {"store.save": "fsync"},
    }
    pkg = make_pkg(tmp_path, {
        "low/e.py": (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self, store):\n"
            "        self.ckpt_lock = threading.RLock()\n"
            "        self.store = store\n"
            "    def sweep(self, k, rec):\n"
            "        with self.ckpt_lock:\n"
            "            self.store.save(k, rec)\n"
        ),
    })
    found = blocking.run(load_package(pkg), cfg)
    assert [f.rule for f in found] == ["blocking-under-lock"]
    assert "store.save" in found[0].message


def test_blocking_under_lock_config_validation(tmp_path):
    pkg = make_pkg(tmp_path, {"low/e.py": "X = 1\n"})
    with pytest.raises(ValueError, match="unknown deny"):
        blocking.run(load_package(pkg), {
            "critical_locks": [{"lock": "l", "deny": ["disk"]}],
        })
    with pytest.raises(ValueError, match="unknown categories"):
        blocking.run(load_package(pkg), {
            "critical_locks": [{"lock": "l", "deny": ["fsync"]}],
            "blocking_calls": {"x.y": "disk"},
        })


def test_blocking_under_lock_noncritical_lock_silent(tmp_path):
    pkg = make_pkg(tmp_path, {"low/e.py": BLOCK_BAD})
    assert blocking.run(load_package(pkg), {"critical_locks": []}) == []


# ---------------------------------------------------------------------------
# Pass 11: mesh-safety
# ---------------------------------------------------------------------------

MESH_HEADER = (
    "import jax\n"
    "import numpy as np\n"
    "from jax.sharding import Mesh, PartitionSpec as P\n"
    "from jax import shard_map\n"
    "mesh = Mesh(np.array([]), ('docs',))\n"
)


def test_mesh_axis_unknown_fires_and_declared_axis_silent(tmp_path):
    pkg = make_pkg(tmp_path / "bad", {
        "low/k.py": MESH_HEADER + (
            "def k(x, axis='doc'):\n"            # typo'd axis
            "    return jax.lax.psum(x, axis)\n"
        ),
    })
    found = mesh_safety.run(load_package(pkg), None)
    assert [f.rule for f in found] == ["mesh-axis-unknown"]
    assert "'doc'" in found[0].detail

    pkg_good = make_pkg(tmp_path / "good", {
        "low/k.py": MESH_HEADER + (
            "SEG_AXIS = 'segs'\n"
            "mesh2 = Mesh(np.array([]), ('docs', SEG_AXIS))\n"
            "def k(x, axis='docs'):\n"
            "    return jax.lax.psum(x, axis)\n"
            "def k2(x):\n"
            "    return jax.lax.all_gather(x, SEG_AXIS)\n"   # constant resolves
        ),
    })
    assert mesh_safety.run(load_package(pkg_good), None) == []


def test_mesh_axis_resolves_against_innermost_function(tmp_path):
    """A kernel closure nested in a factory resolves ITS OWN param
    defaults (review regression: calls were attributed to the outermost
    def, so the factory's unrelated `axis` default shadowed the
    kernel's — a spurious finding on the mesh_seg_program-style
    closure idiom, and a hidden one in the mirror case)."""
    pkg = make_pkg(tmp_path / "good", {
        "low/k.py": MESH_HEADER + (
            "def make(axis='legacy'):\n"              # unrelated default
            "    def kern(x, axis='docs'):\n"
            "        return jax.lax.psum(x, axis)\n"
            "    return kern\n"
        ),
    })
    assert mesh_safety.run(load_package(pkg), None) == []

    pkg2 = make_pkg(tmp_path / "bad", {
        "low/k.py": MESH_HEADER + (
            "def make(axis='docs'):\n"                # outer is fine...
            "    def kern(x, axis='doc'):\n"          # ...inner typo'd
            "        return jax.lax.psum(x, axis)\n"
            "    return kern\n"
        ),
    })
    found = mesh_safety.run(load_package(pkg2), None)
    assert [f.rule for f in found] == ["mesh-axis-unknown"]


def test_mesh_in_specs_arity(tmp_path):
    pkg = make_pkg(tmp_path, {
        "low/m.py": MESH_HEADER + (
            "def step(a, b):\n"
            "    return a\n"
            "bad = shard_map(step, mesh=mesh, in_specs=(P('docs'),),\n"
            "                out_specs=P('docs'))\n"
            "good = shard_map(step, mesh=mesh,\n"
            "                 in_specs=(P('docs'), P('docs')),\n"
            "                 out_specs=P('docs'))\n"
        ),
    })
    found = mesh_safety.run(load_package(pkg), None)
    assert [f.rule for f in found] == ["mesh-in-specs-arity"]
    assert "1" in found[0].message and "2" in found[0].message


def test_mesh_donate_replicated_out_literal(tmp_path):
    pkg = make_pkg(tmp_path / "bad", {
        "low/m.py": MESH_HEADER + (
            "def step(a, b):\n"
            "    return a\n"
            "prog = jax.jit(\n"
            "    shard_map(step, mesh=mesh, in_specs=(P('docs'), P('docs')),\n"
            "              out_specs=P()),\n"      # replicated output
            "    donate_argnums=(0,),\n"           # + donation = the bug
            ")\n"
        ),
    })
    found = mesh_safety.run(load_package(pkg), None)
    assert [f.rule for f in found] == ["mesh-donate-replicated-out"]

    # Twins: donation off, or sharded out_specs — both silent.
    pkg2 = make_pkg(tmp_path / "nodonate", {
        "low/m.py": MESH_HEADER + (
            "def step(a, b):\n"
            "    return a\n"
            "prog = jax.jit(\n"
            "    shard_map(step, mesh=mesh, in_specs=(P('docs'), P('docs')),\n"
            "              out_specs=P()),\n"
            "    donate_argnums=(),\n"
            ")\n"
        ),
    })
    assert mesh_safety.run(load_package(pkg2), None) == []
    pkg3 = make_pkg(tmp_path / "sharded", {
        "low/m.py": MESH_HEADER + (
            "def step(a, b):\n"
            "    return a\n"
            "prog = jax.jit(\n"
            "    shard_map(step, mesh=mesh, in_specs=(P('docs'), P('docs')),\n"
            "              out_specs=P('docs')),\n"
            "    donate_argnums=(0,),\n"
            ")\n"
        ),
    })
    assert mesh_safety.run(load_package(pkg3), None) == []


DECLARED_PROG = (
    "import jax\n"
    "from jax import shard_map\n"
    "def seg_prog(fn, mesh, specs, donate=False):\n"
    "    m = shard_map(fn, mesh=mesh, in_specs=(specs,), out_specs=specs)\n"
    "    return jax.jit(m, donate_argnums=(0,) if donate else ())\n"
)


def test_mesh_declared_replicated_program_guards_donation(tmp_path):
    scope = {"replicated_out_programs": ["fixturepkg/low/m.py::seg_prog"]}
    pkg = make_pkg(tmp_path / "off", {"low/m.py": DECLARED_PROG})
    assert mesh_safety.run(load_package(pkg), scope) == []

    # The "re-enable donation" edit: flip the default -> the rule fires
    # (the conditional donate_argnums resolves through the param default).
    pkg2 = make_pkg(tmp_path / "on", {
        "low/m.py": DECLARED_PROG.replace("donate=False", "donate=True"),
    })
    found = mesh_safety.run(load_package(pkg2), scope)
    assert [f.rule for f in found] == ["mesh-donate-replicated-out"]
    assert "seg_prog" in found[0].detail


def test_mesh_scope_stale_entry_fails_loudly(tmp_path):
    pkg = make_pkg(tmp_path, {"low/m.py": "X = 1\n"})
    with pytest.raises(ValueError, match="matches no function"):
        mesh_safety.run(load_package(pkg), {
            "replicated_out_programs": ["fixturepkg/low/m.py::gone"],
        })
    # And the real package's layers.json does pin mesh_seg_program.
    real_cfg = json.loads((PKG / "analysis" / "layers.json").read_text())
    assert real_cfg["mesh_scope"]["replicated_out_programs"] == [
        "fluidframework_tpu/parallel/mesh.py::mesh_seg_program"
    ]


# ---------------------------------------------------------------------------
# CLI: --changed-only + per-pass timing
# ---------------------------------------------------------------------------

def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_cli_changed_only_scopes_to_git_diff(tmp_path, capsys):
    pkg = _one_finding_pkg(tmp_path)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    # Clean working tree: the (committed) legacy finding is out of scope.
    assert check_cli.main([str(pkg), "--changed-only"]) == 0
    capsys.readouterr()
    # Touch the offending module: the finding is back in the pre-commit
    # loop, exit 1.
    src = pkg / "low" / "util.py"
    src.write_text(src.read_text() + "# touched\n")
    assert check_cli.main([str(pkg), "--changed-only", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["changed_only"] is True and out["n_changed"] >= 1
    assert [f["file"] for f in out["findings"]] == ["fixturepkg/low/util.py"]
    # An UNTRACKED new module is "changed" too (pre-commit covers adds).
    src.write_text("X = 1\n")
    (pkg / "low" / "fresh.py").write_text("from ..high import svc\n")
    assert check_cli.main([str(pkg), "--changed-only"]) == 1
    capsys.readouterr()


def test_cli_changed_only_outside_git_is_usage_error(tmp_path, capsys,
                                                     monkeypatch):
    pkg = _one_finding_pkg(tmp_path)
    monkeypatch.setenv("GIT_DIR", str(tmp_path / "nope" / ".git"))
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path))
    assert check_cli.main([str(pkg), "--changed-only"]) == 2
    assert "git" in capsys.readouterr().err


def test_run_all_reports_per_pass_wall_time(tmp_path, capsys):
    pkg = _one_finding_pkg(tmp_path)
    result = check_cli.run_all(pkg)
    assert set(result["pass_times_ms"]) == set(check_cli.PASSES)
    assert all(t >= 0 for t in result["pass_times_ms"].values())
    # Subset runs time only their passes; --json carries the block.
    result = check_cli.run_all(pkg, rules=["layer-check"])
    assert set(result["pass_times_ms"]) == {"layer-check"}
    check_cli.main([str(pkg), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert set(out["pass_times_ms"]) == set(check_cli.PASSES)


def _copy_pkg(tmp_path: Path) -> Path:
    dst = tmp_path / "fluidframework_tpu"
    shutil.copytree(
        PKG, dst,
        ignore=shutil.ignore_patterns("__pycache__", "*.pyc", "*.so"),
    )
    return dst


SEEDINGS = [
    # (target rel path, transform, expected rule, pass to run)
    ("utils/config.py",
     lambda s: s + "\nfrom ..server import scribe as _seeded\n",
     "layer-upward-import", "layer-check"),
    # PR 19 moved the mark schema to protocol.mark_schema precisely so the
    # rebase kernel no longer imports the dds changeset classes — re-adding
    # that upward edge from the kernel layer must fail loudly (the retired
    # baseline entry no longer shields it).
    ("ops/tree_kernel.py",
     lambda s: s + "\nfrom ..dds.tree import changeset as _seeded\n",
     "layer-upward-import", "layer-check"),
    # loadgen sits in the service layer: an upward import FROM a state-
    # layer module INTO loadgen must trip the gate (proves the new
    # subsystem is really declared, not silently outside the graph).
    ("models/placement.py",
     lambda s: s + "\nfrom ..loadgen import schedule as _seeded\n",
     "layer-upward-import", "layer-check"),
    ("server/scribe.py",
     lambda s: s.replace("for doc in sorted(set(self.docs) | set(self.refs)):",
                         "for doc in set(self.docs) | set(self.refs):"),
     "det-set-iteration", "determinism"),
    ("models/doc_batch_engine.py",
     lambda s: s + (
         "\n\ndef _seeded_bad(state, ops, pays):\n"
         "    out = _fleet_megastep(state, ops, pays)\n"
         "    return state.text_end, out\n"
     ),
     "donate-use-after-dispatch", "donation"),
    ("models/doc_batch_engine.py",
     lambda s: s + (
         "\n\n@jax.jit\ndef _seeded_branch(state):\n"
         "    if state.text_end > 0:\n"
         "        return state\n"
         "    return state\n"
     ),
     "jit-branch-on-tracer", "jit-safety"),
    ("server/launcher.py",
     lambda s: s.replace(
         "            time.sleep(0.2)",
         "            self.shards[0].restarts += 1\n            time.sleep(0.2)"),
     "thread-unlocked-write", "threads"),
    ("server/fleet_main.py",
     lambda s: s + (
         "\n\ndef _seeded_swallow(fc):\n"
         "    try:\n"
         "        fc.step()\n"
         "    except RuntimeError:\n"
         "        pass\n"
     ),
     "swallowed-exception", "swallowed-exception"),
    ("dds/tree/mark_pool.py",
     lambda s: s + (
         "\n\ndef _seeded_churn(pool, counts):\n"
         "    out = []\n"
         "    for c in counts:\n"
         "        out.append(Skip(c))\n"
         "    return pool_marks(pool, out)\n"
     ),
     "fold-mark-churn", "fold-mark-churn"),
    # An AB/BA inversion of the engines' real lock pair, planted in the
    # module that really manipulates both (shared_locks unification).
    ("models/recovery.py",
     lambda s: s + (
         "\n\ndef _seeded_order_a(engine):\n"
         "    with engine.ckpt_lock:\n"
         "        with engine._ckpt_io_lock:\n"
         "            pass\n"
         "\n\ndef _seeded_order_b(engine):\n"
         "    with engine._ckpt_io_lock:\n"
         "        with engine.ckpt_lock:\n"
         "            pass\n"
     ),
     "lock-order-cycle", "lock-order"),
    # A supervisor-side counter reset that forgot the heartbeat's lock —
    # the heartbeat thread writes _renewals under LeaseHeartbeat._lock.
    ("server/failover.py",
     lambda s: s + (
         "\n\ndef _seeded_reset(hb: LeaseHeartbeat) -> None:\n"
         "    hb._renewals = 0\n"
     ),
     "lock-inconsistent-guard", "lock-consistency"),
    # A durable fsync planted under the serving lock: the exact PR 12 law
    # the blocking pass now enforces (ckpt_lock denies fsync).
    ("models/doc_batch_engine.py",
     lambda s: s + (
         "\n\ndef _seeded_fsync(engine, fd):\n"
         "    import os as _os\n"
         "    with engine.ckpt_lock:\n"
         "        _os.fsync(fd)\n"
     ),
     "blocking-under-lock", "blocking-under-lock"),
    # A durable fsync planted inside the shared placement plane's
    # reservation window: PlacementPlane._lock is a leaf every serving
    # read convoys on, so it denies ALL blocking categories (PR 16).
    ("models/placement.py",
     lambda s: s.replace(
         "    def require_migratable(",
         "    def _seeded_fsync(self, fd):\n"
         "        import os as _os\n"
         "        with self._lock:\n"
         "            _os.fsync(fd)\n"
         "\n"
         "    def require_migratable(",
     ),
     "blocking-under-lock", "blocking-under-lock"),
    # A lazy native g++ build planted under the serving lock:
    # ingest_native.warm spawns a compiler subprocess (blocking_calls in
    # layers.json), and ckpt_lock denies subprocess — the exact hazard the
    # warm()/loaded() split keeps out of the engines' serving path.
    ("models/recovery.py",
     lambda s: s + (
         "\n\ndef _seeded_lazy_build(engine):\n"
         "    from ..native import ingest_native\n"
         "    with engine.ckpt_lock:\n"
         "        ingest_native.warm()\n"
     ),
     "blocking-under-lock", "blocking-under-lock"),
    # The "re-enable donation" edit on the declared replicated-out
    # program: flipping mesh_seg_program's default trips mesh-safety (and
    # the named regression test in test_segment_parallel.py).
    ("parallel/mesh.py",
     lambda s: s.replace("donate: bool = False", "donate: bool = True"),
     "mesh-donate-replicated-out", "mesh-safety"),
]


@pytest.mark.parametrize("rel,transform,rule,passname",
                         SEEDINGS, ids=[s[2] for s in SEEDINGS])
def test_seeded_violation_fails_the_real_tree(tmp_path, rel, transform, rule,
                                              passname):
    """Acceptance: seeding each hazard class into a copy of the committed
    tree exits nonzero with the correct rule id and file:line.  Each case
    runs only its own pass (the full-suite clean run is
    test_package_is_clean; this keeps tier-1 inside its budget)."""
    pkg = _copy_pkg(tmp_path)
    target = pkg / rel
    src = target.read_text()
    seeded = transform(src)
    assert seeded != src, "seeding transform did not apply"
    target.write_text(seeded)
    result = check_cli.run_all(pkg, rules=[passname])
    hits = [f for f in result["findings"] if f.rule == rule]
    assert hits, (
        f"seeded {rule} in {rel} not caught; findings: "
        + ", ".join(f"{f.rule}@{f.file}:{f.line}" for f in result["findings"])
    )
    assert any(f.file.endswith(rel) and f.line > 0 for f in hits)


def test_console_entry_point_runs():
    """`python -m fluidframework_tpu.analysis.cli <pkg>` (the console-script
    body) exits 0 on the committed tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.analysis.cli", str(PKG)],
        capture_output=True, text=True, cwd=str(REPO), timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout
