"""weedlint: the repo's unified static-analysis framework.

One engine (tools/weedlint/engine.py), one rule registry, one CLI:

    python -m tools.weedlint                 # whole repo, every rule
    python -m tools.weedlint --rule W501     # one rule
    python -m tools.weedlint --json          # stable machine output
    python -m tools.weedlint --list-rules    # the rule table
    python -m tools.weedlint --update-baseline

Rules (see README "Static analysis" for the full table):

    W001  waiver hygiene (stale / reason-less waivers)    [engine]
    W101  py3.10 runtime compatibility                    [ported]
    W201  tracing chokepoint coverage                     [ported]
    W301  async-drain hot-loop discipline                 [ported]
    W401  degraded-signal table consistency               [ported]
    W501  lockset: guarded attribute outside its lock     [new]
    W502  lockset: unannotated mutation in threaded class [new]
    W503  lock-order cycles over the call graph
    W504  blocking call reachable under a held lock
    W505  blocking call reachable from an event-loop callback
    W601  route query-param parsing must 400, not 500     [new]
    W701  fault-point registry consistency + test cover   [new]
    W801  ec/ resource acquire without release-on-all-paths [new]
    W901  outbound calls must carry an explicit timeout

Waive a finding inline with a reason:

    x = self._cursor  # weedlint: disable=W501 <why this is safe>
"""

from .engine import (Finding, Repo, Rule, RunResult,  # noqa: F401
                     all_rules, get_rule, main, run)
