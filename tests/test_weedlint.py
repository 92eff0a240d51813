"""weedlint (tools/weedlint) as THE tier-1 static-analysis gate.

One engine now carries every repo lint: the four ported rules
(W101 py310 / W201 tracing / W301 async-drain / W401 health-keys), the
lockset thread-safety checker (W501/W502), the interprocedural
call-graph rules (W503 lock-order deadlock / W504 blocking-under-lock
over tools/weedlint/callgraph.py), and the route-param (W601),
fault-registry (W701) and ec-resource (W801) rules.  This suite:

  - proves EVERY rule fires on a planted violation and stays quiet on
    the matching clean source (parametrized, one case per rule);
  - unit-tests the lockset checker on synthetic classes (guarded-ok,
    unguarded-read, waived, stale-waiver, two-lock, holds-contract);
  - unit-tests the call graph (self/attr/module/import resolution,
    spawn edges, unresolved-call conservatism) and both
    interprocedural rules (ABBA + three-class-via-holds cycles,
    diamond no-cycle, every W504 blocking category, the lock-io
    waiver, two-hop reachability anchored at the under-lock call);
  - pins the engine machinery (waivers, baseline, JSON output incl.
    callgraph_stats, --changed-only scoping, CLI exit codes) and the
    repo-wide call-graph resolution ratio;
  - asserts the REPO-WIDE run is clean modulo the committed baseline —
    which the W502 burn-down emptied, and a test keeps empty.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.weedlint import engine  # noqa: E402
from tools.weedlint import rules_py310, rules_tracing  # noqa: E402
from tools.weedlint.callgraph import build_from_sources  # noqa: E402
from tools.weedlint.rules_blocking import check_blocking  # noqa: E402
from tools.weedlint.rules_lockorder import check_lock_order  # noqa: E402
from tools.weedlint.rules_async_drain import \
    check_drain_fault_source  # noqa: E402
from tools.weedlint.rules_faults import (check_registry,  # noqa: E402
                                         hit_sites, load_registry)
from tools.weedlint.rules_lockset import check_class_source  # noqa: E402
from tools.weedlint.rules_resources import \
    check_module_source as check_resources  # noqa: E402
from tools.weedlint.rules_routes import \
    check_module_source as check_routes  # noqa: E402
from tools.weedlint.rules_eventloop import check_eventloop  # noqa: E402
from tools.weedlint.rules_leader import \
    check_source as check_leader_gated  # noqa: E402
from tools.weedlint.rules_timeouts import \
    check_source as check_timeouts  # noqa: E402

# --- planted sources, one clean/bad pair per single-module rule -------------

W301_CLEAN = (
    "def f():\n"
    "    with tr.span('pipeline.drain'):\n"
    "        faultinject.hit('ec.drain')\n")
W301_BAD = (
    "def f():\n"
    "    faultinject.hit('ec.drain')\n")

W501_CLEAN = (
    "import threading\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._n = 0  # guarded-by: _lock\n"
    "        self._t = threading.Thread(target=self._loop)\n"
    "    def _loop(self):\n"
    "        with self._lock:\n"
    "            self._n += 1\n"
    "    def read(self):\n"
    "        with self._lock:\n"
    "            return self._n\n")
W501_BAD = W501_CLEAN.replace(
    "    def read(self):\n"
    "        with self._lock:\n"
    "            return self._n\n",
    "    def read(self):\n"
    "        return self._n\n")

W502_CLEAN = W501_CLEAN
W502_BAD = (
    "import threading\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self.hits = 0\n"
    "        self._t = threading.Thread(target=self._loop)\n"
    "    def _loop(self):\n"
    "        self.hits += 1\n")

# W503: ABBA deadlock across two classes vs the same classes locking in
# one global order
W503_BAD = (
    "import threading\n"
    "class A:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.b = B()\n"
    "    def push(self):\n"
    "        with self._lock:\n"
    "            self.b.notify()\n"
    "    def stats(self):\n"
    "        with self._lock:\n"
    "            return 1\n"
    "class B:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.a = A()\n"
    "    def notify(self):\n"
    "        with self._lock:\n"
    "            pass\n"
    "    def drain(self):\n"
    "        with self._lock:\n"
    "            self.a.stats()\n")
W503_CLEAN = W503_BAD.replace(
    "    def drain(self):\n"
    "        with self._lock:\n"
    "            self.a.stats()\n",
    "    def drain(self):\n"
    "        with self._lock:\n"
    "            pass\n")

W504_BAD = (
    "import threading, time\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "    def outer(self):\n"
    "        with self._lock:\n"
    "            time.sleep(5)\n")
W504_CLEAN = W504_BAD.replace(
    "        with self._lock:\n"
    "            time.sleep(5)\n",
    "        with self._lock:\n"
    "            pass\n"
    "        time.sleep(5)\n")

# W505: a `# loop-callback` reactor method reaching a blocking call vs
# the same work parked on the dispatch pool via a nested closure
W505_BAD = (
    "import time\n"
    "class R:\n"
    "    def _on_readable(self, conn):  # loop-callback\n"
    "        self._helper()\n"
    "    def _helper(self):\n"
    "        time.sleep(1)\n")
W505_CLEAN = (
    "import time\n"
    "class R:\n"
    "    def _on_readable(self, conn):  # loop-callback\n"
    "        def run():\n"
    "            self._helper()\n"
    "        self.submit(run)\n"
    "    def submit(self, fn):\n"
    "        pass\n"
    "    def _helper(self):\n"
    "        time.sleep(1)\n")

W601_CLEAN = (
    "def install(router):\n"
    "    @router.route('GET', '/x')\n"
    "    def handler(req):\n"
    "        try:\n"
    "            limit = int(req.query.get('limit') or 0)\n"
    "        except ValueError:\n"
    "            raise HttpError(400, 'bad limit')\n"
    "        return limit\n")
W601_BAD = (
    "def install(router):\n"
    "    @router.route('GET', '/x')\n"
    "    def handler(req):\n"
    "        return int(req.query.get('limit') or 0)\n")

W801_CLEAN = (
    "def f(path):\n"
    "    with open(path, 'rb') as fh:\n"
    "        return fh.read()\n")
W801_BAD = (
    "def f(path):\n"
    "    fh = open(path, 'rb')\n"
    "    return fh.read()\n")

W901_CLEAN = (
    "def f(url):\n"
    "    a = http_json('GET', url, timeout=10.0)\n"
    "    b = http_bytes('GET', url, None, None, 5.0)\n"
    "    c = urlopen(url, timeout=3.0)\n"
    "    d = socket.create_connection(('h', 1), 2.0)\n"
    "    return a, b, c, d\n")
W901_BAD = (
    "def f(url):\n"
    "    return http_json('GET', url)\n")

W902_CLEAN = (
    "class M:\n"
    "    def _apply(self, data):  # raft-apply\n"
    "        self.coordinator.apply_replicated(data)\n"
    "    def _replicate(self, doc):\n"
    "        if not self.raft.is_leader:\n"
    "            return\n"
    "        self.raft.append('alert', doc)\n"
    "    def _promote(self, role):\n"
    "        if role == 'leader':\n"
    "            self.coordinator.resume_replicated()\n"
    "    def _journal(self, rec,  # leader-only\n"
    "                 sync=False):\n"
    "        self.replicate_fn(rec)\n"
    "    def harmless(self, items):\n"
    "        items.append(1)\n")  # list .append never matches
W902_BAD = (
    "class M:\n"
    "    def handle(self, req):\n"
    "        self.raft.append('event', {'events': []})\n"
    "        self.alert_engine.import_state({})\n")

CASES = [
    ("W101", "x = 1\n", "import tomllib\n",
     lambda src: rules_py310.check_source(src, "t.py")),
    ("W201", "import urllib.parse\n", "import urllib.request\n",
     lambda src: rules_tracing.check_package_source(src, "pkg/t.py")),
    ("W301", W301_CLEAN, W301_BAD,
     lambda src: check_drain_fault_source(src, "t.py")),
    ("W501", W501_CLEAN, W501_BAD,
     lambda src: check_class_source(src, "t.py")),
    ("W502", W502_CLEAN, W502_BAD,
     lambda src: check_class_source(src, "t.py")),
    ("W503", W503_CLEAN, W503_BAD,
     lambda src: check_lock_order(build_from_sources([("pkg/t.py", src)]))),
    ("W504", W504_CLEAN, W504_BAD,
     lambda src: check_blocking(build_from_sources([("pkg/t.py", src)]))),
    ("W505", W505_CLEAN, W505_BAD,
     lambda src: check_eventloop(build_from_sources([("pkg/t.py", src)]))),
    ("W601", W601_CLEAN, W601_BAD,
     lambda src: check_routes(src, "t.py")),
    ("W801", W801_CLEAN, W801_BAD,
     lambda src: check_resources(src, "t.py")),
    ("W901", W901_CLEAN, W901_BAD,
     lambda src: check_timeouts(src, "t.py")),
    ("W902", W902_CLEAN, W902_BAD,
     lambda src: check_leader_gated(src, "seaweedfs_tpu/master/t.py")),
]


@pytest.mark.parametrize("rule_id,clean,bad,checker", CASES,
                         ids=[c[0] for c in CASES])
def test_rule_fires_on_planted_violation_and_passes_clean(
        rule_id, clean, bad, checker):
    assert [f for f in checker(clean) if f.rule == rule_id] == [], rule_id
    hits = [f for f in checker(bad) if f.rule == rule_id]
    assert hits, f"{rule_id} did not fire on its planted violation"
    assert all(f.line > 0 for f in hits)


# --- W701: fault-registry consistency (tables as arguments) -----------------

class TestFaultRegistry:
    REG = {"a.b": 3, "c.d": 4}

    def test_consistent_tables_pass(self):
        sites = [("a.b", 10, "m.py"), ("c.d", 11, "m.py")]
        assert check_registry(self.REG, 1, sites, '"a.b" "c.d"') == []

    def test_unregistered_site_caught(self):
        sites = [("a.b", 10, "m.py"), ("c.d", 11, "m.py"),
                 ("typo.name", 12, "m.py")]
        out = check_registry(self.REG, 1, sites, '"a.b" "c.d"')
        assert any("typo.name" in f.message and f.path == "m.py"
                   for f in out)

    def test_registered_without_site_caught(self):
        out = check_registry(self.REG, 1, [("a.b", 10, "m.py")],
                             '"a.b" "c.d"')
        assert any("c.d" in f.message and "never inject" in f.message
                   for f in out)

    def test_untested_point_caught(self):
        sites = [("a.b", 10, "m.py"), ("c.d", 11, "m.py")]
        out = check_registry(self.REG, 1, sites, '"a.b" only')
        assert any("c.d" in f.message and "not exercised" in f.message
                   for f in out)

    def test_live_registry_parses_and_matches_sites(self):
        fi_path = os.path.join(REPO, "seaweedfs_tpu", "utils",
                               "faultinject.py")
        with open(fi_path, encoding="utf-8") as f:
            src = f.read()
        registry, _line = load_registry(src)
        assert "ec.drain" in registry and "ec.shard.corrupt" in registry
        # the module's own hit() implementation is not a SITE
        assert all(n for n, _ln in hit_sites(src, fi_path))


# --- lockset checker on synthetic classes -----------------------------------

class TestLockset:
    def test_two_lock_class_wrong_lock_caught(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "        self._a = 0  # guarded-by: _a_lock\n"
            "        self._b = 0  # guarded-by: _b_lock\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._a_lock:\n"
            "            self._a += 1\n"
            "            self._b += 1\n"  # wrong lock held
            "    def read(self):\n"
            "        with self._b_lock:\n"
            "            return self._b\n"
            "        with self._a_lock:\n"
            "            return self._a\n")
        out = [f for f in check_class_source(src, "t.py")
               if f.rule == "W501"]
        assert len(out) == 1 and "self._b" in out[0].message \
            and "_b_lock" in out[0].message

    def test_holds_annotation_honored(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n"
            "    def _bump(self):  # holds: _lock\n"
            "        self._n += 1\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n")
        assert check_class_source(src, "t.py") == []

    def test_locked_suffix_honored(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._bump_locked()\n"
            "    def _bump_locked(self):\n"
            "        self._n += 1\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._bump_locked()\n")
        assert check_class_source(src, "t.py") == []

    def test_thread_entry_annotation_creates_root(self):
        # no lexical Thread() construction: the annotation alone must
        # make the hook method a root so the naked access is caught
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "    def on_event(self, ev):  # thread-entry\n"
            "        self._n += 1\n")
        out = check_class_source(src, "t.py")
        assert any(f.rule == "W501" and "on_event" in f.message
                   for f in out)

    def test_concurrent_class_marks_public_methods_as_roots(self):
        src = (
            "import threading\n"
            "class C:  # weedlint: concurrent-class\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "    def bump(self):\n"
            "        self._n += 1\n"
            "    def read(self):\n"
            "        return self._n\n")
        out = [f for f in check_class_source(src, "t.py")
               if f.rule == "W501"]
        assert len(out) == 2  # both naked accesses race each other

    def test_closure_does_not_inherit_lock(self):
        # a nested function may run on another thread after the with
        # released the lock: the access inside it counts as unlocked
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def make(self):\n"
            "        with self._lock:\n"
            "            def cb():\n"
            "                return self._n\n"
            "            return cb\n")
        out = [f for f in check_class_source(src, "t.py")
               if f.rule == "W501"]
        assert len(out) == 1 and out[0].rule == "W501"

    def test_init_is_exempt(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "        self._n = 1\n"  # naked in __init__: fine
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n")
        assert check_class_source(src, "t.py") == []


# --- callgraph: resolution rules + conservatism ------------------------------

class TestCallGraph:
    def _graph(self, src: str, extra=None):
        sources = [("pkg/t.py", src)] + list(extra or [])
        return build_from_sources(sources)

    def test_self_method_resolution(self):
        g = self._graph(
            "class A:\n"
            "    def f(self):\n"
            "        self.g()\n"
            "    def g(self):\n"
            "        pass\n")
        assert "pkg/t.py::A.g" in g.edges()["pkg/t.py::A.f"]

    def test_attr_typed_cross_class_resolution(self):
        g = self._graph(
            "class Helper:\n"
            "    def work(self):\n"
            "        pass\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self.h = Helper()\n"
            "    def f(self):\n"
            "        self.h.work()\n")
        assert "pkg/t.py::Helper.work" in g.edges()["pkg/t.py::A.f"]

    def test_module_function_resolution(self):
        g = self._graph(
            "def helper():\n"
            "    pass\n"
            "def top():\n"
            "    helper()\n")
        assert "pkg/t.py::helper" in g.edges()["pkg/t.py::top"]

    def test_cross_module_import_resolution(self):
        g = self._graph(
            "from pkg.other import helper\n"
            "def top():\n"
            "    helper()\n",
            extra=[("pkg/other.py", "def helper():\n    pass\n")])
        assert "pkg/other.py::helper" in g.edges()["pkg/t.py::top"]

    def test_constructor_resolves_to_init(self):
        g = self._graph(
            "class A:\n"
            "    def __init__(self):\n"
            "        pass\n"
            "def make():\n"
            "    return A()\n")
        assert "pkg/t.py::A.__init__" in g.edges()["pkg/t.py::make"]

    def test_unresolvable_call_is_counted_not_edged(self):
        g = self._graph(
            "class A:\n"
            "    def f(self):\n"
            "        self.on_event()\n")  # hook attr, never constructed
        assert g.edges()["pkg/t.py::A.f"] == set()
        assert g.calls_unresolved == 1

    def test_stdlib_call_counts_external(self):
        g = self._graph(
            "import os\n"
            "def f():\n"
            "    os.getpid()\n")
        assert g.calls_external == 1 and g.calls_unresolved == 0

    def test_thread_target_is_spawn_edge(self):
        g = self._graph(
            "import threading\n"
            "class A:\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run).start()\n"
            "    def _run(self):\n"
            "        pass\n")
        assert "pkg/t.py::A._run" in g.edges()["pkg/t.py::A.start"]
        # ...but spawn edges are excluded from lock propagation walks
        assert "pkg/t.py::A._run" not in g.sync_edges()["pkg/t.py::A.start"]

    def test_stats_shape(self):
        g = self._graph("def f():\n    pass\n")
        s = g.stats()
        assert set(s) >= {"nodes", "edges", "calls_total",
                          "calls_resolved", "calls_external",
                          "calls_unresolved", "unresolved_ratio"}


# --- W503: lock-order cycles --------------------------------------------------

class TestLockOrder:
    def test_three_class_cycle_through_holds_contract(self):
        # the B._lock -> C._lock edge exists ONLY because _kick's
        # `# holds:` contract says B._lock is held on entry — no
        # lexical `with` covers the call into C
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.b = B()\n"
            "    def step(self):\n"
            "        with self._lock:\n"
            "            self.b.enter()\n"
            "    def back(self):\n"
            "        with self._lock:\n"
            "            return 1\n"
            "class B:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.c = C()\n"
            "    def enter(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def _kick(self):  # holds: _lock\n"
            "        self.c.poke()\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.a = A()\n"
            "    def poke(self):\n"
            "        with self._lock:\n"
            "            self.a.back()\n")
        out = check_lock_order(build_from_sources([("pkg/t.py", src)]))
        assert len(out) == 1
        msg = out[0].message
        for lock in ("A._lock", "B._lock", "C._lock"):
            assert lock in msg, msg
        # the hint carries the acquisition-path evidence, including the
        # hop through C that only the holds: contract makes visible
        assert "acquisition path" in out[0].hint
        assert "c.poke" in out[0].hint

    def test_diamond_without_cycle_is_clean(self):
        src = (
            "import threading\n"
            "class D:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def leaf(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "class B:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.d = D()\n"
            "    def mid(self):\n"
            "        with self._lock:\n"
            "            self.d.leaf()\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.d = D()\n"
            "    def mid(self):\n"
            "        with self._lock:\n"
            "            self.d.leaf()\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.b = B()\n"
            "        self.c = C()\n"
            "    def top(self):\n"
            "        with self._lock:\n"
            "            self.b.mid()\n"
            "            self.c.mid()\n")
        assert check_lock_order(
            build_from_sources([("pkg/t.py", src)])) == []

    def test_lexical_self_nesting_of_plain_lock_caught(self):
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            with self._lock:\n"
            "                pass\n")
        out = check_lock_order(build_from_sources([("pkg/t.py", src)]))
        assert len(out) == 1 and "A._lock" in out[0].message

    def test_rlock_self_nesting_is_fine(self):
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            with self._lock:\n"
            "                pass\n")
        assert check_lock_order(
            build_from_sources([("pkg/t.py", src)])) == []


# --- W504: blocking while a lock is held --------------------------------------

def _w504(src: str):
    return check_blocking(build_from_sources([("pkg/t.py", src)]))


_CLS = ("import threading, time, queue, subprocess\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._q = queue.Queue(8)\n"
        "        self._uq = queue.Queue()\n"
        "        self._ev = threading.Event()\n")

W504_CATEGORY_CASES = [
    ("sleep", "        with self._lock:\n            time.sleep(1)\n",
     "        time.sleep(1)\n"),
    ("http-egress",
     "        with self._lock:\n            http_json('GET', u)\n",
     "        http_json('GET', u)\n"),
    ("queue-get", "        with self._lock:\n            self._q.get()\n",
     "        with self._lock:\n            self._q.get(timeout=1)\n"),
    ("queue-put",
     "        with self._lock:\n            self._q.put(1)\n",
     # unbounded queue put never blocks: clean even under the lock
     "        with self._lock:\n            self._uq.put(1)\n"),
    ("event-wait",
     "        with self._lock:\n            self._ev.wait()\n",
     "        with self._lock:\n            self._ev.wait(1.0)\n"),
    ("subprocess",
     "        with self._lock:\n            subprocess.run(['x'])\n",
     "        subprocess.run(['x'])\n"),
    ("file-read",
     "        fh = open('x')\n"
     "        with self._lock:\n            fh.read()\n",
     "        fh = open('x')\n"
     "        with self._lock:\n            fh.read(4096)\n"),
]


class TestBlockingUnderLock:
    @pytest.mark.parametrize("cat,bad,clean", W504_CATEGORY_CASES,
                             ids=[c[0] for c in W504_CATEGORY_CASES])
    def test_category_fires_and_clean_passes(self, cat, bad, clean):
        bad_src = _CLS + "    def m(self, u=None):\n" + bad
        clean_src = _CLS + "    def m(self, u=None):\n" + clean
        hits = _w504(bad_src)
        assert hits and all(f.rule == "W504" for f in hits), cat
        assert _w504(clean_src) == [], cat

    def test_two_hop_reachability_anchors_at_origin(self):
        src = (
            "import threading, time\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self.mid()\n"
            "    def mid(self):\n"
            "        self.leaf()\n"
            "    def leaf(self):\n"
            "        time.sleep(5)\n")
        out = _w504(src)
        assert len(out) == 1
        f = out[0]
        assert f.line == 7  # the under-lock self.mid() call, not leaf
        assert "C.leaf" in f.message and "sleep" in f.message
        assert "call chain" in f.hint and "C.mid" in f.hint

    def test_holds_contract_counts_as_lock_held(self):
        src = (
            "import threading, time\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def _flush(self):  # holds: _lock\n"
            "        time.sleep(1)\n")
        out = _w504(src)
        assert len(out) == 1 and "holds:" in out[0].message

    def test_lock_io_waiver_honored(self):
        src = _CLS + (
            "    def m(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1)  "
            "# weedlint: lock-io audited: bounded bench-only pause\n")
        assert _w504(src) == []

    def test_lock_io_waiver_without_reason_is_flagged(self):
        src = _CLS + (
            "    def m(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1)  # weedlint: lock-io\n")
        out = _w504(src)
        assert len(out) == 1 and "no reason" in out[0].message

    def test_thread_spawn_does_not_carry_lock(self):
        src = (
            "import threading, time\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def start(self):\n"
            "        with self._lock:\n"
            "            threading.Thread(target=self._run).start()\n"
            "    def _run(self):\n"
            "        time.sleep(5)\n")
        assert _w504(src) == []


# --- engine: waivers, baseline, run -----------------------------------------

# --- W505: blocking reachable from event-loop callbacks ----------------------

class TestEventLoopRule:
    def _check(self, src):
        return check_eventloop(build_from_sources([("pkg/t.py", src)]))

    def test_disk_helper_category(self):
        hits = self._check(
            "import os\n"
            "class R:\n"
            "    def _flush(self, conn):  # loop-callback\n"
            "        os.pread(3, 10, 0)\n")
        assert hits and "disk" in hits[0].message

    def test_loop_io_waiver_honored_and_reasonless_flagged(self):
        waived = (
            "import time\n"
            "class R:\n"
            "    def _cb(self):  # loop-callback\n"
            "        time.sleep(1)  # weedlint: loop-io cache-probed,"
            " cannot block\n")
        assert self._check(waived) == []
        hits = self._check(waived.replace(
            " cache-probed, cannot block", ""))
        assert hits and "no reason" in hits[0].message

    def test_inner_loop_callback_not_rewalked_from_outer(self):
        # the blocking call inside _inner (its own loop-callback root)
        # anchors at _inner, not duplicated at _outer's call site
        src = (
            "import time\n"
            "class R:\n"
            "    def _outer(self):  # loop-callback\n"
            "        self._inner()\n"
            "    def _inner(self):  # loop-callback\n"
            "        time.sleep(1)\n")
        hits = self._check(src)
        assert len(hits) == 1 and "_inner" in hits[0].message

    def test_spawned_thread_target_is_off_loop(self):
        src = (
            "import time, threading\n"
            "class R:\n"
            "    def _cb(self):  # loop-callback\n"
            "        threading.Thread(target=self._work).start()\n"
            "    def _work(self):\n"
            "        time.sleep(1)\n")
        assert self._check(src) == []

    def test_shipped_eventloop_module_is_clean(self):
        res = engine.run(REPO, rule_ids=["W505"])
        assert [f for f in res.findings if f.rule == "W505"] == []
        # and the rule actually has roots to walk (the reactor methods
        # are marked) — an empty root set would make the clean run
        # vacuous
        import re as _re

        src = open(os.path.join(
            REPO, "seaweedfs_tpu", "utils", "eventloop.py"),
            encoding="utf-8").read()
        assert len(_re.findall(r"#\s*loop-callback", src)) >= 8


def _mini_repo(tmp_path, body: str) -> str:
    """A throwaway repo: one package module + empty baseline."""
    pkg = tmp_path / "seaweedfs_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(body)
    (tmp_path / "tools").mkdir()
    (tmp_path / "tools" / "weedlint_baseline.json").write_text(
        '{"version": 1, "findings": {}}')
    return str(tmp_path)


# rules that judge a tiny synthetic tree on its own terms (no live
# package import, no this-repo-specific file contracts)
FAST_RULES = ["W101", "W501", "W502", "W601", "W801"]


class TestEngine:
    def test_waiver_suppresses_with_reason(self, tmp_path):
        body = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.hits = 0\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.hits += 1  "
            "# weedlint: disable=W502 single scan thread owns it\n")
        root = _mini_repo(tmp_path, body)
        res = engine.run(root, rule_ids=FAST_RULES)
        assert [f.rule for f in res.findings] == []
        assert len(res.waived) == 1

    def test_waiver_without_reason_is_flagged(self, tmp_path):
        body = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.hits = 0\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.hits += 1  # weedlint: disable=W502\n")
        root = _mini_repo(tmp_path, body)
        res = engine.run(root)
        assert any(f.rule == "W001" and "no reason" in f.message
                   for f in res.findings)

    def test_stale_waiver_is_flagged(self, tmp_path):
        body = "x = 1  # weedlint: disable=W801 leftover excuse\n"
        root = _mini_repo(tmp_path, body)
        res = engine.run(root)
        assert any(f.rule == "W001" and "stale waiver" in f.message
                   for f in res.findings)

    def test_docstring_quoting_waiver_syntax_is_not_a_waiver(self,
                                                            tmp_path):
        body = ('"""Docs: waive with  # weedlint: disable=W501 why"""\n'
                "x = 1\n")
        root = _mini_repo(tmp_path, body)
        res = engine.run(root)
        assert [f for f in res.findings if f.rule == "W001"] == []

    def test_baseline_grandfathers_exact_count(self, tmp_path):
        body = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.hits = 0\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.hits += 1\n"
            "        self.hits += 2\n")
        root = _mini_repo(tmp_path, body)
        res = engine.run(root, rule_ids=FAST_RULES)
        assert len(res.findings) == 2
        bl = str(tmp_path / "bl.json")
        engine.save_baseline(bl, res.findings)
        res2 = engine.run(root, rule_ids=FAST_RULES, baseline_path=bl)
        assert res2.findings == [] and len(res2.baselined) == 2
        # a THIRD identical violation exceeds the grandfathered count
        mod = tmp_path / "seaweedfs_tpu" / "mod.py"
        mod.write_text(mod.read_text() + "        self.hits += 3\n")
        res3 = engine.run(root, rule_ids=FAST_RULES, baseline_path=bl)
        assert len(res3.findings) == 1

    def test_unknown_rule_id_raises(self, tmp_path):
        root = _mini_repo(tmp_path, "x = 1\n")
        with pytest.raises(KeyError):
            engine.run(root, rule_ids=["W999"])

    def test_json_output_schema(self, tmp_path):
        root = _mini_repo(tmp_path, "import tomllib\n")
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--json",
             "--rule", "W101", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 1
        doc = json.loads(p.stdout)
        assert doc["version"] == 1
        assert doc["rules"] == ["W101"]
        assert doc["counts"]["reported"] == len(doc["findings"]) == 1
        f = doc["findings"][0]
        assert set(f) >= {"rule", "path", "line", "message",
                          "fingerprint"}
        assert f["path"].endswith("mod.py")

    def test_cli_list_rules(self):
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--list-rules"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 0
        for rid in ("W101", "W201", "W301", "W401", "W501", "W502",
                    "W601", "W701", "W801", "W901"):
            assert rid in p.stdout

    def test_cli_unknown_rule_exits_2(self):
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--rule", "W999"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 2

    def test_json_carries_callgraph_stats_for_interprocedural_rules(
            self, tmp_path):
        root = _mini_repo(tmp_path, "x = 1\n")
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--json",
             "--rule", "W504", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        doc = json.loads(p.stdout)
        s = doc["callgraph_stats"]
        assert set(s) >= {"nodes", "edges", "calls_total",
                          "calls_unresolved", "unresolved_ratio"}

    def test_changed_only_scopes_reporting_to_changed_files(self,
                                                            tmp_path):
        violation = (
            "import threading\n"
            "class C{n}:\n"
            "    def __init__(self):\n"
            "        self.hits = 0\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.hits += 1\n")
        root = _mini_repo(tmp_path, violation.format(n=1))
        g = ["git", "-C", root, "-c", "user.email=t@t", "-c",
             "user.name=t"]
        subprocess.run(g + ["init", "-q"], check=True, timeout=60)
        subprocess.run(g + ["add", "-A"], check=True, timeout=60)
        subprocess.run(g + ["commit", "-qm", "seed"], check=True,
                       timeout=60)
        # a NEW (untracked) file with the same violation
        (tmp_path / "seaweedfs_tpu" / "newmod.py").write_text(
            violation.format(n=2))
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--changed-only",
             "HEAD", "--rule", "W502", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 1
        assert "newmod.py" in p.stdout
        assert "/mod.py:" not in p.stdout  # committed file not reported
        assert "changed vs HEAD only" in p.stderr

    def test_changed_only_works_from_a_git_subdirectory(self, tmp_path):
        """The lint root nested below the git toplevel: git diff must
        emit ROOT-relative paths (--relative) or every finding would be
        silently filtered away and the fast path would pass real
        regressions."""
        sub = tmp_path / "sub"
        sub.mkdir()
        root = _mini_repo(sub, "x = 1\n")
        g = ["git", "-C", str(tmp_path), "-c", "user.email=t@t", "-c",
             "user.name=t"]
        subprocess.run(g + ["init", "-q"], check=True, timeout=60)
        subprocess.run(g + ["add", "-A"], check=True, timeout=60)
        subprocess.run(g + ["commit", "-qm", "seed"], check=True,
                       timeout=60)
        # a TRACKED file modified with a violation, under the subdir
        (sub / "seaweedfs_tpu" / "mod.py").write_text(
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.hits = 0\n"
            "        self._t = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.hits += 1\n")
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint", "--changed-only",
             "HEAD", "--rule", "W502", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 1, p.stdout + p.stderr
        assert "mod.py" in p.stdout

    def test_update_baseline_rejects_changed_only(self, tmp_path):
        """A baseline regenerated from a filtered finding set would
        delete every other grandfathered entry — refuse the combo."""
        root = _mini_repo(tmp_path, "x = 1\n")
        p = subprocess.run(
            [sys.executable, "-m", "tools.weedlint",
             "--update-baseline", "--changed-only", "HEAD", root],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert p.returncode == 2
        assert "cannot be combined" in p.stderr


# --- the repo-wide tier-1 gate ----------------------------------------------

class TestWholeRepo:
    def test_repo_is_clean_modulo_baseline(self):
        """THE gate: every rule over the whole repo, zero findings
        beyond waivers and the committed baseline."""
        res = engine.run(REPO)
        assert res.findings == [], "\n".join(
            f.render() for f in res.findings)

    def test_baseline_carries_only_grandfathered_lockset_findings(self):
        """The committed baseline is a W502 grandfather list for
        pre-weedlint modules — route-param, resource and registry
        findings were all FIXED, not baselined, and new-rule findings
        must never be added here (fix or waive instead)."""
        with open(os.path.join(REPO, "tools",
                               "weedlint_baseline.json")) as f:
            doc = json.load(f)
        kinds = {e["rule"] for e in doc["findings"].values()}
        assert kinds <= {"W502"}, kinds

    def test_callgraph_resolution_stays_healthy(self):
        """A resolution regression silently blinds W503/W504, so the
        repo-wide unresolved ratio is pinned (recorded bound: 0.50 —
        currently ~0.42; raise the bound only with an explanation of
        what got less resolvable)."""
        res = engine.run(REPO, rule_ids=["W503", "W504"])
        s = res.callgraph_stats
        assert s is not None
        assert s["nodes"] > 1000 and s["edges"] > 1500
        assert s["unresolved_ratio"] <= 0.50, s

    def test_baseline_is_empty_after_the_w502_burn_down(self):
        """PR 11 burned the 37-entry W502 grandfather list down to
        zero: every finding is now fixed or carries a reasoned waiver.
        Nothing must ever be baselined again — fix it or waive it."""
        with open(os.path.join(REPO, "tools",
                               "weedlint_baseline.json")) as f:
            doc = json.load(f)
        assert doc["findings"] == {}

    def test_shell_fault_list_prints_registry(self):
        from seaweedfs_tpu.shell.commands import COMMANDS
        from seaweedfs_tpu.utils import faultinject as fi

        out = COMMANDS["fault.list"](None, {})
        for name in fi.FAULT_POINTS:
            assert name in out
        doc = json.loads(COMMANDS["fault.list"](None, {"json": "true"}))
        assert doc == dict(fi.list_points())
