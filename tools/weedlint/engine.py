"""weedlint core: one engine under every repo lint.

Before this module the repo carried four standalone AST lints
(check_py310 / check_tracing / check_async_drain / check_health_keys),
each re-implementing file discovery, AST walking, waiver comments, and
its own CLI.  weedlint hoists the shared machinery into one place:

  - Repo/FileCtx: file discovery (``.gitignore`` directory patterns +
    generated-file markers honored) with ONE cached ``ast.parse`` per
    file, shared by every rule;
  - Rule registry: each rule has a stable id (``W101`` ...), a summary
    for the rule table, and returns structured ``Finding``s
    (file:line + message + fix hint);
  - inline waivers: ``# weedlint: disable=W501 <reason>`` on the
    offending line suppresses that rule there.  A waiver must carry a
    reason, and a waiver whose line no longer triggers the named rule is
    itself a finding (stale waivers rot into false documentation);
  - a committed baseline (tools/weedlint_baseline.json) for
    grandfathered findings, so a new rule can land strict without a
    flag-day: baselined findings are reported as suppressed, NEW
    findings still fail.

CLI (python -m tools.weedlint):

    python -m tools.weedlint [root] [--rule W501[,W502]] [--json]
                             [--update-baseline] [--baseline PATH]
                             [--list-rules] [--changed-only [REF]]

``--changed-only`` (the pre-commit fast path) restricts REPORTED
findings to files changed vs the git ref (default HEAD, worktree diff
plus untracked); analysis still covers the whole repo, because the
interprocedural rules (W503/W504 over the cached call graph in
callgraph.py) need the whole program to be right.

Exit 0 = clean (after waivers + baseline), 1 = findings, 2 = usage.
The ``--json`` document is stable and documented (README "Static
analysis") so future tooling can diff findings across PRs.
"""

from __future__ import annotations

import ast
import fnmatch
import hashlib
import json
import os
import re
import sys
from typing import Callable, Iterable, Optional

SKIP_DIRS = {".git", "__pycache__", ".claude", ".pytest_cache",
             "node_modules", ".venv", "venv", ".hypothesis"}
# files carrying these markers in their first lines are machine-written:
# findings in them are noise nobody can fix by hand
GENERATED_MARKERS = ("@generated", "DO NOT EDIT")

BASELINE_REL = os.path.join("tools", "weedlint_baseline.json")

_WAIVER_RE = re.compile(
    r"#\s*weedlint:\s*disable=([A-Z0-9,\s]+?)(?:\s+(.*))?$")

# the engine's own rule id: waiver hygiene (stale / reason-less waivers)
WAIVER_RULE_ID = "W001"


class Finding:
    """One structured lint finding.  ``line`` is 1-based (0 = whole
    file); the fingerprint (rule + path + message, line-independent) is
    what the baseline keys on, so findings survive unrelated edits."""

    __slots__ = ("rule", "path", "line", "message", "hint")

    def __init__(self, rule: str, path: str, line: int, message: str,
                 hint: str = ""):
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.message = message
        self.hint = hint

    @property
    def fingerprint(self) -> str:
        h = hashlib.sha1(
            f"{self.rule}|{self.path}|{self.message}".encode())
        return h.hexdigest()[:12]

    def to_dict(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "message": self.message, "fingerprint": self.fingerprint}
        if self.hint:
            d["hint"] = self.hint
        return d

    def render(self) -> str:
        s = f"{self.path}:{self.line}: {self.rule}: {self.message}"
        if self.hint:
            s += f"  [hint: {self.hint}]"
        return s


class FileCtx:
    """One repo file, parsed at most once no matter how many rules
    look at it."""

    def __init__(self, root: str, rel: str):
        self.root = root
        self.rel = rel
        self.path = os.path.join(root, rel)
        self._source: Optional[str] = None
        self._lines: Optional[list[str]] = None
        self._tree = None
        self._tree_err: Optional[SyntaxError] = None
        self._parsed = False

    @property
    def source(self) -> str:
        if self._source is None:
            with open(self.path, encoding="utf-8", errors="replace") as f:
                self._source = f.read()
        return self._source

    @property
    def lines(self) -> list[str]:
        if self._lines is None:
            self._lines = self.source.splitlines()
        return self._lines

    def line(self, lineno: int) -> str:
        return self.lines[lineno - 1] if 0 < lineno <= len(self.lines) \
            else ""

    @property
    def tree(self) -> Optional[ast.AST]:
        """Cached parse; None when the file does not parse (the W101
        rule reports the SyntaxError, everything else skips)."""
        if not self._parsed:
            self._parsed = True
            try:
                self._tree = ast.parse(self.source, filename=self.rel)
            except SyntaxError as e:
                self._tree_err = e
        return self._tree

    @property
    def parse_error(self) -> Optional[SyntaxError]:
        self.tree
        return self._tree_err


def _gitignore_dir_patterns(root: str) -> list[str]:
    """Directory patterns from .gitignore (``name/`` entries and plain
    names) — the shared-discovery exclusion the four old lints each
    approximated with a hardcoded set."""
    out: list[str] = []
    try:
        with open(os.path.join(root, ".gitignore"),
                  encoding="utf-8") as f:
            for raw in f:
                pat = raw.strip()
                if not pat or pat.startswith("#"):
                    continue
                if pat.endswith("/"):
                    out.append(pat.rstrip("/"))
                elif "." not in pat and "*" not in pat:
                    out.append(pat)
    except OSError:
        pass
    return out


class Repo:
    """File discovery + shared parse cache for one lint run."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._files: Optional[list[FileCtx]] = None
        self._ignored_dirs = _gitignore_dir_patterns(self.root)

    def _skip_dir(self, name: str) -> bool:
        if name in SKIP_DIRS:
            return True
        return any(fnmatch.fnmatch(name, pat)
                   for pat in self._ignored_dirs)

    def files(self) -> list[FileCtx]:
        """Every tracked .py file, sorted, generated files excluded."""
        if self._files is None:
            out: list[FileCtx] = []
            for dirpath, dirnames, filenames in os.walk(self.root):
                dirnames[:] = sorted(d for d in dirnames
                                     if not self._skip_dir(d))
                for name in sorted(filenames):
                    if not name.endswith(".py"):
                        continue
                    rel = os.path.relpath(os.path.join(dirpath, name),
                                          self.root)
                    ctx = FileCtx(self.root, rel)
                    try:
                        head = ctx.source[:400]
                    except OSError:
                        continue
                    if any(m in head for m in GENERATED_MARKERS):
                        continue
                    out.append(ctx)
            self._files = out
        return self._files

    def package_files(self, package: str = "seaweedfs_tpu") -> list[FileCtx]:
        prefix = package + os.sep
        return [f for f in self.files() if f.rel.startswith(prefix)]

    def test_files(self) -> list[FileCtx]:
        return [f for f in self.files()
                if f.rel.startswith("tests" + os.sep)]

    def get(self, rel: str) -> Optional[FileCtx]:
        for f in self.files():
            if f.rel == rel:
                return f
        return None


class Rule:
    """Base class: subclasses set id/name/summary and implement
    check(repo) -> list[Finding]."""

    id = "W000"
    name = "base"
    summary = ""
    hint = ""

    def check(self, repo: Repo) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, path: str, line: int, message: str,
                hint: Optional[str] = None) -> Finding:
        return Finding(self.id, path, line, message,
                       self.hint if hint is None else hint)


_REGISTRY: dict[str, Rule] = {}


def register(rule_cls: type) -> type:
    """Class decorator: instantiate + index by rule id."""
    rule = rule_cls()
    if rule.id in _REGISTRY:  # pragma: no cover - programming error
        raise ValueError(f"duplicate rule id {rule.id}")
    _REGISTRY[rule.id] = rule
    return rule_cls


def all_rules() -> list[Rule]:
    _load_builtin_rules()
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def get_rule(rule_id: str) -> Optional[Rule]:
    _load_builtin_rules()
    return _REGISTRY.get(rule_id)


_loaded = False


def _load_builtin_rules() -> None:
    """Import the rule modules exactly once (registration side effect)."""
    global _loaded
    if _loaded:
        return
    _loaded = True
    from . import (rules_async_drain, rules_blocking,  # noqa: F401
                   rules_eventloop, rules_faults,
                   rules_health_keys, rules_leader, rules_ledger,
                   rules_lockorder, rules_lockset, rules_py310,
                   rules_resources, rules_routes, rules_timeouts,
                   rules_tracing)


# --- waivers -----------------------------------------------------------------

class Waiver:
    __slots__ = ("path", "line", "ids", "reason", "used")

    def __init__(self, path: str, line: int, ids: set[str], reason: str):
        self.path = path
        self.line = line
        self.ids = ids
        self.reason = reason
        self.used: set[str] = set()


def _comment_lines(ctx: FileCtx) -> dict[int, str]:
    """lineno -> comment text, via tokenize so a docstring QUOTING the
    waiver syntax (this engine's own docs, the README examples) is
    never mistaken for a live waiver."""
    import io
    import tokenize

    out: dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(
                io.StringIO(ctx.source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, SyntaxError, IndentationError):
        pass  # unparseable file: W101 reports it; no waivers here
    return out


def collect_waivers(files: Iterable[FileCtx]) -> list[Waiver]:
    out: list[Waiver] = []
    for ctx in files:
        if "weedlint:" not in ctx.source:
            continue
        for i, comment in sorted(_comment_lines(ctx).items()):
            if "weedlint:" not in comment:
                continue
            m = _WAIVER_RE.search(comment)
            if m is None:
                continue
            ids = {s.strip() for s in m.group(1).split(",") if s.strip()}
            out.append(Waiver(ctx.rel, i, ids, (m.group(2) or "").strip()))
    return out


def apply_waivers(findings: list[Finding],
                  waivers: list[Waiver]) -> tuple[list[Finding],
                                                  list[Finding],
                                                  list[Finding]]:
    """-> (kept, waived, waiver_findings).  A waiver suppresses matching
    findings on its own line; stale or reason-less waivers become W001
    findings so waivers cannot rot silently."""
    index: dict[tuple[str, int], list[Waiver]] = {}
    for w in waivers:
        index.setdefault((w.path, w.line), []).append(w)
    kept: list[Finding] = []
    waived: list[Finding] = []
    for f in findings:
        ws = index.get((f.path, f.line), [])
        hit = next((w for w in ws if f.rule in w.ids), None)
        if hit is not None:
            hit.used.add(f.rule)
            waived.append(f)
        else:
            kept.append(f)
    extra: list[Finding] = []
    for w in waivers:
        stale = sorted(w.ids - w.used)
        if stale:
            extra.append(Finding(
                WAIVER_RULE_ID, w.path, w.line,
                f"stale waiver: disable={','.join(stale)} suppresses "
                f"nothing on this line any more — delete it",
                "a waiver that outlives its finding is false "
                "documentation"))
        if w.used and not w.reason:
            extra.append(Finding(
                WAIVER_RULE_ID, w.path, w.line,
                f"waiver disable={','.join(sorted(w.used))} has no "
                f"reason — say WHY the finding is a false positive",
                "# weedlint: disable=W501 <why this is safe>"))
    return kept, waived, extra


# --- baseline ----------------------------------------------------------------

def load_baseline(path: str) -> dict[str, dict]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    return dict(doc.get("findings") or {})


def save_baseline(path: str, findings: list[Finding]) -> dict:
    entries: dict[str, dict] = {}
    for f in findings:
        e = entries.setdefault(f.fingerprint, {
            "rule": f.rule, "path": f.path, "message": f.message,
            "count": 0})
        e["count"] += 1
    doc = {"version": 1,
           "comment": "grandfathered findings; regenerate with "
                      "python -m tools.weedlint --update-baseline. "
                      "Never baseline code added in the same PR — fix "
                      "it or waive it with a reason.",
           "findings": {k: entries[k] for k in sorted(entries)}}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def apply_baseline(findings: list[Finding],
                   baseline: dict[str, dict]) -> tuple[list[Finding],
                                                       list[Finding]]:
    """-> (kept, suppressed).  Each baseline entry forgives up to
    `count` findings with that fingerprint — the grandfather clause,
    never a blank check."""
    budget = {k: int(v.get("count", 1)) for k, v in baseline.items()}
    kept: list[Finding] = []
    suppressed: list[Finding] = []
    for f in findings:
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
            suppressed.append(f)
        else:
            kept.append(f)
    return kept, suppressed


# --- run ---------------------------------------------------------------------

class RunResult:
    def __init__(self, root: str, rules: list[Rule],
                 findings: list[Finding], waived: list[Finding],
                 baselined: list[Finding], files_checked: int,
                 callgraph_stats: Optional[dict] = None):
        self.root = root
        self.rules = rules
        self.findings = findings
        self.waived = waived
        self.baselined = baselined
        self.files_checked = files_checked
        self.callgraph_stats = callgraph_stats

    def to_dict(self) -> dict:
        doc = {
            "version": 1,
            "root": self.root,
            "files_checked": self.files_checked,
            "rules": [r.id for r in self.rules],
            "findings": [f.to_dict() for f in self.findings],
            "counts": {"reported": len(self.findings),
                       "waived": len(self.waived),
                       "baselined": len(self.baselined)},
        }
        if self.callgraph_stats is not None:
            # interprocedural-rule health: a resolution regression
            # (unresolved ratio creeping up) silently blinds W503/W504,
            # so the stats ride every JSON document for test logs to
            # diff (test_weedlint pins the ratio)
            doc["callgraph_stats"] = self.callgraph_stats
        return doc


def changed_files(root: str, ref: str) -> set[str]:
    """ROOT-relative paths changed vs `ref` (worktree diff + untracked)
    — the --changed-only pre-commit fast path's file set.  `--relative`
    matters: findings carry root-relative paths, and when the lint root
    is a subdirectory of the git toplevel a plain `git diff` would emit
    toplevel-relative paths that never intersect them (every finding
    silently filtered away).  `ls-files` is cwd-relative already."""
    import subprocess

    out: set[str] = set()
    for args in (["git", "-C", root, "diff", "--relative",
                  "--name-only", ref],
                 ["git", "-C", root, "ls-files", "--others",
                  "--exclude-standard"]):
        p = subprocess.run(args, capture_output=True, text=True,
                           timeout=60)
        if p.returncode != 0:
            raise RuntimeError(
                f"git failed for --changed-only ({ref}): "
                f"{p.stderr.strip() or p.stdout.strip()}")
        out.update(line.strip() for line in p.stdout.splitlines()
                   if line.strip())
    return out


def run(root: str, rule_ids: Optional[list[str]] = None,
        baseline_path: Optional[str] = None,
        on_rule_error: Optional[Callable[[Rule, Exception], None]] = None,
        ignore_baseline: bool = False,
        paths_filter: Optional[set[str]] = None) -> RunResult:
    """One full lint pass.  `rule_ids` restricts which rules run
    (waiver hygiene always runs); a rule that crashes surfaces as a
    finding against itself instead of killing the run.
    `ignore_baseline` reports the grandfathered findings too — the
    --update-baseline path needs the FULL set, or regenerating on a
    clean repo would wipe every entry and fail the next run.
    `paths_filter` (--changed-only) restricts REPORTED findings to
    those paths; every rule still analyzes the whole repo (the call
    graph and cross-file contracts need the whole program) — only the
    reporting is scoped, so the fast path can never let a cross-file
    regression through into a later full run silently."""
    repo = Repo(root)
    rules = all_rules()
    if rule_ids:
        want = set(rule_ids)
        unknown = want - {r.id for r in rules}
        if unknown:
            raise KeyError(f"unknown rule id(s): {', '.join(sorted(unknown))}")
        rules = [r for r in rules if r.id in want]
    findings: list[Finding] = []
    for rule in rules:
        try:
            findings.extend(rule.check(repo))
        except Exception as e:  # noqa: BLE001 - one broken rule must
            if on_rule_error is not None:  # not hide the others' findings
                on_rule_error(rule, e)
            findings.append(Finding(
                rule.id, BASELINE_REL, 0,
                f"rule {rule.id} crashed: {type(e).__name__}: {e}",
                "fix the rule; a crashed rule fails the run"))
    waivers = collect_waivers(repo.files())
    findings, waived, waiver_findings = apply_waivers(findings, waivers)
    if rule_ids is None or WAIVER_RULE_ID in (rule_ids or []):
        findings.extend(waiver_findings)
    bl_path = baseline_path or os.path.join(repo.root, BASELINE_REL)
    baseline = {} if ignore_baseline else load_baseline(bl_path)
    findings, baselined = apply_baseline(findings, baseline)
    if paths_filter is not None:
        findings = [f for f in findings if f.path in paths_filter]
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    graph = getattr(repo, "_weedlint_callgraph", None)
    return RunResult(repo.root, rules, findings, waived, baselined,
                     len(repo.files()),
                     callgraph_stats=(graph.stats()
                                      if graph is not None else None))


# --- CLI ---------------------------------------------------------------------

def _default_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def main(argv: Optional[list[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = None
    rule_ids: Optional[list[str]] = None
    as_json = False
    update_baseline = False
    baseline_path = None
    changed_ref: Optional[str] = None
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--json":
            as_json = True
        elif a == "--update-baseline":
            update_baseline = True
        elif a == "--changed-only":
            # optional ref argument (defaults to HEAD); a following
            # token that is an existing directory is the ROOT, not a ref
            changed_ref = "HEAD"
            if i + 1 < len(argv) and not argv[i + 1].startswith("-") \
                    and not os.path.isdir(argv[i + 1]):
                i += 1
                changed_ref = argv[i]
        elif a == "--list-rules":
            for r in all_rules():
                print(f"{r.id}  {r.name:<22} {r.summary}")
            return 0
        elif a == "--rule":
            i += 1
            if i >= len(argv):
                print("--rule needs an argument", file=sys.stderr)
                return 2
            # repeated --rule flags accumulate (--rule W503 --rule W504)
            rule_ids = (rule_ids or []) + [
                s.strip() for s in argv[i].split(",") if s.strip()]
        elif a == "--baseline":
            i += 1
            if i >= len(argv):
                print("--baseline needs an argument", file=sys.stderr)
                return 2
            baseline_path = argv[i]
        elif a.startswith("-"):
            print(f"unknown flag {a}", file=sys.stderr)
            return 2
        elif root is None:
            root = a
        else:
            print(f"unexpected argument {a}", file=sys.stderr)
            return 2
        i += 1
    root = root or _default_root()
    # the health-keys rule imports the live tables: the repo under lint
    # must win over any installed copy
    if root not in sys.path:
        sys.path.insert(0, root)
    paths_filter = None
    if changed_ref is not None and update_baseline:
        # a baseline regenerated from a FILTERED finding set would
        # silently delete every other grandfathered entry
        print("--update-baseline cannot be combined with "
              "--changed-only: the baseline must be regenerated from "
              "the full finding set", file=sys.stderr)
        return 2
    if changed_ref is not None:
        try:
            paths_filter = changed_files(root, changed_ref)
        except (RuntimeError, OSError) as e:
            print(str(e), file=sys.stderr)
            return 2
        if not paths_filter:
            print(f"weedlint: no files changed vs {changed_ref}",
                  file=sys.stderr)
            return 0
    try:
        result = run(root, rule_ids, baseline_path,
                     ignore_baseline=update_baseline,
                     paths_filter=paths_filter)
    except KeyError as e:
        print(str(e), file=sys.stderr)
        return 2
    if update_baseline:
        path = baseline_path or os.path.join(result.root, BASELINE_REL)
        save_baseline(path, result.findings)
        print(f"weedlint: baseline written to {path} "
              f"({len(result.findings)} finding(s))", file=sys.stderr)
        return 0
    if as_json:
        print(json.dumps(result.to_dict(), indent=2, sort_keys=True))
    else:
        for f in result.findings:
            print(f.render())
    scope = f", changed vs {changed_ref} only" if changed_ref else ""
    print(f"weedlint: {result.files_checked} files, "
          f"{len(result.rules)} rule(s), "
          f"{len(result.findings)} finding(s) "
          f"({len(result.waived)} waived, "
          f"{len(result.baselined)} baselined{scope})", file=sys.stderr)
    if result.callgraph_stats:
        s = result.callgraph_stats
        print(f"weedlint: callgraph {s['nodes']} nodes, "
              f"{s['edges']} edges, "
              f"{s['calls_unresolved']}/{s['calls_total']} calls "
              f"unresolved ({s['unresolved_ratio']:.0%})",
              file=sys.stderr)
    return 1 if result.findings else 0
