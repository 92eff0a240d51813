"""tools/check_async_drain.py (now a shim over weedlint rule W301)
as a tier-1 gate.

The async multi-buffered drain (PR 7) only pays off while nothing
reintroduces a blocking full-block fetch on the streaming hot loop —
a regression that stays byte-correct and therefore invisible to every
differential test.  These tests (a) pin the checker's detection of
planted regressions, and (b) run it over the WHOLE repo so the real
ec/streaming.py keeps its drain off the critical thread and the
`ec.drain` fault point stays inside the `pipeline.drain` span.
"""

from __future__ import annotations

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "check_async_drain.py")


def _load():
    spec = importlib.util.spec_from_file_location("check_async_drain", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CHECK = _load()

# a minimal streaming.py skeleton satisfying every rule
CLEAN = """
def _encode_file_staged(self):
    def drain_fetch_core(meta):
        with tr.span("pipeline.drain", dispatch=0):
            if faultinject._points:
                faultinject.hit("ec.drain")
            parity = self._fetch(meta)
        return parity
    drainer = AsyncDrainer(drain_fetch_core, lambda m, p: None)
    drainer.finish()

def _encode_file_mesh(self):
    def drain_fetch_dev(meta):
        with tr.span("pipeline.drain", device=0):
            if faultinject._points:
                faultinject.hit("ec.drain")
            parity = self._fetch(meta)
        return parity
    drainers = DrainerGroup(2, drain_fetch_dev, lambda m, p: None)
    drainers.finish()
"""


class TestPlantedViolations:
    def test_clean_skeleton_passes(self):
        assert CHECK.check_streaming_source(CLEAN, "x.py") == []
        assert CHECK.check_drain_fault_source(CLEAN, "x.py") == []

    def test_blocking_fetch_in_hot_loop_rejected(self):
        # the pre-PR-7 shape: _fetch called straight from the loop body
        src = CLEAN.replace(
            "    drainer.finish()\n\ndef _encode_file_mesh",
            "    parity = self._fetch(handle)\n\ndef _encode_file_mesh")
        problems = CHECK.check_streaming_source(src, "x.py")
        assert problems and "_fetch" in problems[0] \
            and "drain" in problems[0]

    def test_blocking_asarray_outside_drainer_rejected(self):
        src = CLEAN.replace("drainer = AsyncDrainer(drain_fetch_core,",
                            "words = np.asarray(out_dev)\n"
                            "    drainer = AsyncDrainer(drain_fetch_core,")
        problems = CHECK.check_streaming_source(src, "x.py")
        assert problems and "asarray" in problems[0]

    def test_missing_async_drainer_rejected(self):
        src = CLEAN.replace(
            "    drainer = AsyncDrainer(drain_fetch_core, lambda m, p: None)\n"
            "    drainer.finish()", "    pass")
        problems = CHECK.check_streaming_source(src, "x.py")
        assert any("AsyncDrainer" in p and "_encode_file_staged" in p
                   for p in problems)

    def test_missing_hot_func_rejected(self):
        problems = CHECK.check_streaming_source("x = 1\n", "x.py")
        assert len(problems) == 2
        assert all("not found" in p for p in problems)

    def test_mesh_without_any_drainer_rejected(self):
        # the per-device plane must construct AsyncDrainer lanes through
        # a DrainerGroup (or AsyncDrainer directly) — neither = finding
        src = CLEAN.replace(
            "    drainers = DrainerGroup(2, drain_fetch_dev, "
            "lambda m, p: None)\n"
            "    drainers.finish()", "    pass")
        problems = CHECK.check_streaming_source(src, "x.py")
        assert any("_encode_file_mesh" in p and "DrainerGroup" in p
                   for p in problems)

    def test_drain_fault_outside_span_rejected(self):
        src = ("def f():\n"
               "    with tr.span(\"pipeline.write\"):\n"
               "        faultinject.hit(\"ec.drain\")\n")
        problems = CHECK.check_drain_fault_source(src, "x.py")
        assert problems and "pipeline.drain" in problems[0]

    def test_drain_fault_with_no_span_at_all_rejected(self):
        src = "def f():\n    faultinject.hit(\"ec.drain\")\n"
        problems = CHECK.check_drain_fault_source(src, "x.py")
        assert problems

    def test_other_fault_points_unconstrained(self):
        src = "def f():\n    faultinject.hit(\"ec.dispatch\")\n"
        assert CHECK.check_drain_fault_source(src, "x.py") == []

    def test_blocking_call_in_nested_drain_helper_accepted(self):
        # a helper nested inside a drain helper inherits the allowance
        src = CLEAN.replace(
            "            parity = self._fetch(meta)",
            "            def inner():\n"
            "                return self._fetch(meta)\n"
            "            parity = inner()")
        assert CHECK.check_streaming_source(src, "x.py") == []


class TestWholeRepo:
    def test_repo_is_clean(self):
        problems = CHECK.check_repo(REPO)
        assert problems == [], "\n".join(problems)

    def test_cli_exit_status(self):
        assert CHECK.main([REPO]) == 0
