"""cephck rule engine: file walking, suppression baseline, reporting.

Rules are small classes (see rules.py) with an ``id``, a ``doc``
explaining how to read a finding, and ``check(ctx)`` yielding
Findings over one parsed file.  The engine owns everything around
them: collecting files, parsing once, matching findings against the
suppression baseline and inline ``# cephck: ignore[rule]`` markers,
and turning the result into an exit code the ship gate can trust.

v2 runs in two phases: every file is parsed first and folded into a
ProjectContext (symbol table + call graph, see project.py), then the
rules run per file with ``ctx.project`` carrying the cross-module
view — so a rule can ask "does this loop call something that host-
syncs two modules away" instead of guessing from one AST.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import pathlib
import sys
from typing import Iterable, Iterator

from .project import ProjectContext, dotted  # noqa: F401  (dotted is
# re-exported: rules and external callers import it from here)

#: directories never scanned: caches, VCS internals, and the fixture
#: corpus (known-bad snippets exist to be red — scanning them would
#: make the tree permanently red)
SKIP_PARTS = {"__pycache__", ".git", "fixtures", ".eggs", "build"}

BASELINE_NAME = ".cephck-baseline.json"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str          # repo-root-relative posix path
    line: int
    symbol: str        # enclosing def/class qualname (or flagged name)
    message: str

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}: {self.rule}{sym}: {self.message}"


class FileContext:
    """One parsed source file plus the cross-file engine options."""

    def __init__(self, path: pathlib.Path, rel: str, source: str,
                 tree: ast.Module, options: dict,
                 project: ProjectContext | None = None):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self.options = options
        #: the cross-module pass (set by the engine before rules run)
        self.project = project
        self._parents: dict[ast.AST, ast.AST] | None = None

    def module(self):
        """This file's ModuleInfo in the project pass (import aliases,
        jit registry) — None only if the engine skipped phase 1."""
        return self.project.module_for(self.rel) if self.project else None

    # -- helpers shared by rules ---------------------------------------

    def parents(self) -> dict[ast.AST, ast.AST]:
        if self._parents is None:
            self._parents = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    self._parents[child] = node
        return self._parents

    def qualname(self, node: ast.AST) -> str:
        """Enclosing class/function qualname for a node (best effort)."""
        parts: list[str] = []
        parents = self.parents()
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    def imports_jax(self) -> bool:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                if any(a.name == "jax" or a.name.startswith("jax.")
                       for a in node.names):
                    return True
            elif isinstance(node, ast.ImportFrom):
                if node.module and (node.module == "jax" or
                                    node.module.startswith("jax.")):
                    return True
        return False

    def finding(self, rule: str, node: ast.AST, message: str,
                symbol: str | None = None) -> Finding:
        return Finding(rule=rule, path=self.rel,
                       line=getattr(node, "lineno", 0),
                       symbol=symbol if symbol is not None
                       else self.qualname(node),
                       message=message)

    def inline_ignored(self, f: Finding) -> bool:
        """``# cephck: ignore[rule]`` on the finding's line (or the
        line directly above) waives it — for one-off sites where a
        baseline entry would outlive the code it excuses."""
        marker = f"cephck: ignore[{f.rule}]"
        for ln in (f.line - 1, f.line - 2):
            if 0 <= ln < len(self.lines) and marker in self.lines[ln]:
                return True
        return False


def repo_root(start: pathlib.Path | None = None) -> pathlib.Path:
    """Nearest ancestor carrying pyproject.toml (falls back to cwd)."""
    cur = (start or pathlib.Path.cwd()).resolve()
    if cur.is_file():
        cur = cur.parent
    for cand in (cur, *cur.parents):
        if (cand / "pyproject.toml").exists():
            return cand
    return pathlib.Path.cwd()


def collect_files(paths: Iterable[str],
                  root: pathlib.Path) -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for p in paths:
        pp = pathlib.Path(p)
        if not pp.is_absolute():
            pp = root / pp
        if pp.is_file() and pp.suffix == ".py":
            out.append(pp)
        elif pp.is_dir():
            for f in sorted(pp.rglob("*.py")):
                if not SKIP_PARTS.intersection(f.parts):
                    out.append(f)
        elif not pp.exists():
            raise FileNotFoundError(f"cephck: no such path: {p}")
    return out


# ------------------------------------------------------------ baseline

class BaselineError(ValueError):
    """Malformed baseline — including any entry without a reason."""


@dataclasses.dataclass
class Suppression:
    rule: str
    path: str
    symbol: str        # "" matches any symbol
    reason: str
    used: int = 0

    def matches(self, f: Finding) -> bool:
        # exact repo-relative path only: a suffix match would let a
        # root "bench.py" entry silently swallow findings from any
        # future tests/bench.py too
        if self.rule != f.rule or f.path != self.path:
            return False
        return self.symbol in ("", f.symbol)


def load_baseline(path: pathlib.Path) -> list[Suppression]:
    """Load and VALIDATE the baseline: every entry must name a rule,
    a path, and a one-line human reason.  An unexplained suppression
    is rejected outright — the baseline is the audit trail for every
    finding the tree is allowed to keep."""
    try:
        data = json.loads(path.read_text())
    except json.JSONDecodeError as ex:
        raise BaselineError(f"{path}: invalid JSON: {ex}") from ex
    entries = data.get("suppressions")
    if not isinstance(entries, list):
        raise BaselineError(f"{path}: expected a 'suppressions' list")
    out = []
    for i, e in enumerate(entries):
        reason = str(e.get("reason", "")).strip()
        rule = str(e.get("rule", "")).strip()
        rel = str(e.get("path", "")).strip()
        if not rule or not rel:
            raise BaselineError(
                f"{path}: suppression #{i} needs 'rule' and 'path'")
        if not reason or "\n" in reason:
            raise BaselineError(
                f"{path}: suppression #{i} ({rule} @ {rel}) needs a "
                "one-line 'reason' — unexplained baseline entries are "
                "not allowed")
        out.append(Suppression(rule=rule, path=rel,
                               symbol=str(e.get("symbol", "")).strip(),
                               reason=reason))
    return out


def prune_baseline(path: pathlib.Path,
                   stale: list[Suppression]) -> int:
    """Rewrite the baseline file dropping `stale` entries (matched by
    rule/path/symbol), preserving everything else verbatim — the
    ``--prune-baseline`` rewrite.  Returns how many entries went."""
    data = json.loads(path.read_text())
    gone = {(s.rule, s.path, s.symbol) for s in stale}
    kept = [e for e in data.get("suppressions", [])
            if (str(e.get("rule", "")).strip(),
                str(e.get("path", "")).strip(),
                str(e.get("symbol", "")).strip()) not in gone]
    dropped = len(data.get("suppressions", [])) - len(kept)
    data["suppressions"] = kept
    path.write_text(json.dumps(data, indent=1) + "\n")
    return dropped


# -------------------------------------------------------------- engine

class Engine:
    def __init__(self, rules, root: pathlib.Path,
                 wire_schema: pathlib.Path | None = None,
                 suppressions: list[Suppression] | None = None):
        self.rules = list(rules)
        self.root = root
        self.options = {
            "wire_schema": wire_schema or
            root / "tests" / "fixtures" / "wire_schema.json",
        }
        self.suppressions = suppressions or []
        self.findings: list[Finding] = []
        self.suppressed: list[tuple[Finding, Suppression]] = []
        self.errors: list[str] = []
        self.scanned: list[str] = []

    def _parse(self, path: pathlib.Path) -> FileContext | None:
        try:
            source = path.read_text()
            tree = ast.parse(source, filename=str(path))
        except (OSError, SyntaxError) as ex:
            self.errors.append(f"{path}: {ex}")
            return None
        try:
            rel = path.resolve().relative_to(self.root).as_posix()
        except ValueError:
            rel = path.as_posix()
        self.scanned.append(rel)
        return FileContext(path, rel, source, tree, self.options)

    def _check_ctx(self, ctx: FileContext) -> Iterator[Finding]:
        for rule in self.rules:
            for f in rule.check(ctx):
                if ctx.inline_ignored(f):
                    continue
                for s in self.suppressions:
                    if s.matches(f):
                        s.used += 1
                        self.suppressed.append((f, s))
                        break
                else:
                    self.findings.append(f)
                    yield f

    def check_file(self, path: pathlib.Path) -> Iterator[Finding]:
        """Single-file scan (fixture tests): the project pass degrades
        to a one-module table, so cross-module rules still run."""
        ctx = self._parse(path)
        if ctx is None:
            return
        project = ProjectContext()
        project.add(ctx.rel, ctx.tree)
        project.finalize()
        ctx.project = project
        yield from self._check_ctx(ctx)

    def run(self, paths: Iterable[str]) -> int:
        # phase 1: parse everything, build the cross-module context
        ctxs: list[FileContext] = []
        project = ProjectContext()
        for f in collect_files(paths, self.root):
            ctx = self._parse(f)
            if ctx is not None:
                project.add(ctx.rel, ctx.tree)
                ctxs.append(ctx)
        project.finalize()
        # phase 2: rules, per file, with the project view attached
        for ctx in ctxs:
            ctx.project = project
            for _ in self._check_ctx(ctx):
                pass
        return 1 if (self.findings or self.errors) else 0

    def stale_suppressions(self) -> list[Suppression]:
        """Unused entries whose path was actually scanned — a partial
        scan (one file) must not cry stale about the rest of the
        baseline."""
        return [s for s in self.suppressions
                if not s.used and s.path in self.scanned]


# ----------------------------------------------------------------- CLI

def sarif_report(rules, findings, errors=(), stale=()) -> dict:
    """SARIF 2.1.0 log for code-scanning uploads (the github format
    annotates the diff; SARIF populates the Security/Code-scanning
    tab and survives as an artifact).  String escaping is json.dumps's
    job — messages with quotes, newlines or %-sequences must round-
    trip verbatim (asserted by tests/test_cephck.py)."""
    fired = {f.rule for f in findings}
    driver_rules = [{
        "id": r.id,
        "shortDescription": {
            "text": (r.doc or r.id).strip().splitlines()[0]},
        "fullDescription": {"text": (r.doc or r.id).strip()},
    } for r in rules if r.id in fired]
    index = {dr["id"]: i for i, dr in enumerate(driver_rules)}
    results = [{
        "ruleId": f.rule,
        "ruleIndex": index[f.rule],
        "level": "error",
        "message": {"text": f.message},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": f.path,
                                     "uriBaseId": "SRCROOT"},
                "region": {"startLine": f.line},
            },
        }],
    } for f in findings]
    notifications = [
        {"level": "error", "message": {"text": e}} for e in errors
    ] + [
        {"level": "error",
         "message": {"text": f"stale suppression ({s.rule} @ {s.path})"
                             f" no longer matches any finding"}}
        for s in stale
    ]
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "cephck",
                "rules": driver_rules,
            }},
            "originalUriBaseIds": {"SRCROOT": {"uri": "file:///"}},
            "invocations": [{
                "executionSuccessful": not (errors or stale),
                "toolExecutionNotifications": notifications,
            }],
            "results": results,
        }],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m ceph_tpu.analysis",
        description="cephck: project-specific static analysis "
                    "(exit 0 = clean, 1 = findings, 2 = bad config)")
    ap.add_argument("paths", nargs="*",
                    default=["ceph_tpu", "tests", "scripts", "bench.py",
                             "chip_smoke.py"],
                    help="files/dirs to scan (default: the whole tree)")
    ap.add_argument("--baseline", default=None,
                    help=f"suppression baseline (default: "
                         f"<repo-root>/{BASELINE_NAME})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report everything")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="rewrite the baseline dropping stale entries "
                         "(file/rule pairs that no longer produce a "
                         "finding); without this flag stale entries "
                         "FAIL the run — the blindfold only shrinks")
    ap.add_argument("--wire-schema", default=None,
                    help="wire schema lockfile (default: "
                         "tests/fixtures/wire_schema.json)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings on stdout "
                         "(alias for --format json)")
    ap.add_argument("--format", default=None, dest="fmt",
                    choices=("text", "json", "github", "sarif"),
                    help="findings output: text (default), json "
                         "(one machine-readable document), github "
                         "(::error workflow annotations for CI), or "
                         "sarif (2.1.0 log for code-scanning uploads)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print every rule id + one-line summary")
    ap.add_argument("--explain", metavar="RULE",
                    help="print a rule's full doc (how to read and "
                         "fix its findings)")
    args = ap.parse_args(argv)

    from .rules import ALL_RULES
    rules = [cls() for cls in ALL_RULES]

    if args.list_rules:
        for r in rules:
            first = (r.doc or "").strip().splitlines()[0]
            print(f"{r.id:22s} {first}")
        return 0
    if args.explain:
        for r in rules:
            if r.id == args.explain:
                print(f"{r.id}\n{'=' * len(r.id)}\n{r.doc.strip()}")
                return 0
        print(f"cephck: unknown rule {args.explain!r}", file=sys.stderr)
        return 2

    root = repo_root()
    suppressions: list[Suppression] = []
    bpath = None
    if not args.no_baseline:
        bpath = pathlib.Path(args.baseline) if args.baseline \
            else root / BASELINE_NAME
        if bpath.exists():
            try:
                suppressions = load_baseline(bpath)
            except BaselineError as ex:
                print(f"cephck: {ex}", file=sys.stderr)
                return 2
        elif args.baseline:
            print(f"cephck: baseline not found: {bpath}", file=sys.stderr)
            return 2

    wire = pathlib.Path(args.wire_schema) if args.wire_schema else None
    eng = Engine(rules, root, wire_schema=wire, suppressions=suppressions)
    try:
        rc = eng.run(args.paths)
    except FileNotFoundError as ex:
        print(ex, file=sys.stderr)
        return 2

    stale = eng.stale_suppressions()
    if stale and args.prune_baseline and bpath and bpath.exists():
        prune_baseline(bpath, stale)
        for s in stale:
            print(f"cephck: pruned stale suppression "
                  f"({s.rule} @ {s.path})", file=sys.stderr)
        stale = []

    fmt = args.fmt or ("json" if args.as_json else "text")
    if fmt == "json":
        print(json.dumps({
            "findings": [dataclasses.asdict(f) for f in eng.findings],
            "suppressed": len(eng.suppressed),
            "stale": [dataclasses.asdict(s) for s in stale],
            "errors": eng.errors,
        }, indent=1))
    elif fmt == "sarif":
        print(json.dumps(sarif_report(rules, eng.findings,
                                      eng.errors, stale), indent=1))
    elif fmt == "github":
        # GitHub Actions workflow commands: each finding becomes an
        # inline annotation on the PR diff.  Newlines/percent must be
        # URL-style escaped per the workflow-command grammar.
        def esc(s: str) -> str:
            return s.replace("%", "%25").replace("\r", "%0D") \
                    .replace("\n", "%0A")
        for f in eng.findings:
            print(f"::error file={f.path},line={f.line},"
                  f"title=cephck {f.rule}::{esc(f.message)}")
        for e in eng.errors:
            print(f"::error title=cephck parse error::{esc(e)}")
        for s in stale:
            print(f"::error file={s.path},title=cephck stale "
                  f"suppression::{esc(s.rule)} no longer matches any "
                  f"finding — remove it or run --prune-baseline")
        print(f"cephck: {len(eng.findings)} finding(s), "
              f"{len(eng.suppressed)} suppressed by baseline",
              file=sys.stderr)
    else:
        for f in eng.findings:
            print(f.render())
        for e in eng.errors:
            print(f"cephck: parse error: {e}", file=sys.stderr)
        for s in stale:
            print(f"cephck: stale suppression ({s.rule} @ {s.path}) "
                  f"no longer matches any finding — remove it or run "
                  f"--prune-baseline", file=sys.stderr)
        n = len(eng.findings)
        print(f"cephck: {n} finding(s), {len(eng.suppressed)} "
              f"suppressed by baseline"
              + (f", {len(eng.errors)} parse error(s)"
                 if eng.errors else "")
              + (f", {len(stale)} STALE suppression(s)"
                 if stale else ""))
    if stale and rc == 0:
        # a suppression nothing matches is a blindfold over code that
        # moved: the gate fails until the baseline shrinks to fit
        rc = 1
    return rc
