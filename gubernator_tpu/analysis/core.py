"""guberlint core: rule registry, repo index, waivers, findings.

The repo's load-bearing disciplines — donated-buffer reads under the
engine lock, no blocking calls inside a lock scope, GUBER_* knobs flowing
through envconf -> example.conf -> docs, escape hatches with differential
tests, metric/event/fault registries in sync with their docs — existed
only as convention and review memory. This package turns each one into a
machine-checked invariant: every rule is grounded in a real historical
bug (docs/static-analysis.md catalogues them), `make lint` runs the set,
and tests/test_lint.py makes zero-findings-on-HEAD a tier-1 gate.

Waiver syntax (inline, justification REQUIRED after ``--``)::

    x = backend.state  # guberlint: disable=lock-discipline -- stub backend has no lock

A waiver on its own line covers the next code line; a file-scoped
variant (``guberlint: file-disable`` with the same ``=rule -- why``
tail) anywhere in the file covers the whole file. A waiver without a
justification is itself a finding (rule ``waiver-syntax``) — the
justification is the reviewable artifact.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# `#` for python/conf, `//` for the C++ sources
WAIVER_RE = re.compile(
    r"(?:#|//)\s*guberlint:\s*(file-)?disable=([a-z0-9_,-]+)"
    r"\s*(?:--\s*(.*?))?\s*$")

# anything that looks like a waiver attempt but fails WAIVER_RE is a
# malformed waiver, reported rather than silently ignored
_WAIVERISH_RE = re.compile(r"(?:#|//)\s*guberlint:\s*(?:file-)?disable")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a concrete location."""

    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class Waiver:
    rule: str
    line: int  # line the waiver comment sits on
    file_scope: bool
    justification: str

    def covers(self, rule: str, line: int) -> bool:
        if self.rule not in (rule, "all"):
            return False
        # same line, or a standalone waiver comment covering the next line
        return self.file_scope or line in (self.line, self.line + 1)


class SourceFile:
    """One scanned file: text, lines, lazy AST, parsed waivers."""

    def __init__(self, root: str, relpath: str):
        self.root = root
        self.relpath = relpath
        with open(os.path.join(root, relpath), encoding="utf-8",
                  errors="replace") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self._tree: Optional[ast.Module] = None
        self._tree_error: Optional[str] = None
        self.waivers: List[Waiver] = []
        self.waiver_findings: List[Finding] = []
        self._parse_waivers()

    @property
    def tree(self) -> Optional[ast.Module]:
        if self._tree is None and self._tree_error is None:
            try:
                self._tree = ast.parse(self.text)
            except SyntaxError as e:  # non-Python or broken file
                self._tree_error = str(e)
        return self._tree

    def _parse_waivers(self) -> None:
        for i, line in enumerate(self.lines, 1):
            m = WAIVER_RE.search(line)
            if not m:
                if _WAIVERISH_RE.search(line):
                    self.waiver_findings.append(Finding(
                        "waiver-syntax", self.relpath, i,
                        "unparseable guberlint waiver (want a comment of "
                        "the form 'guberlint: "
                        "disable=<rule-id> -- <justification>')"))
                continue
            file_scope = bool(m.group(1))
            rules = [r for r in m.group(2).split(",") if r]
            justification = (m.group(3) or "").strip()
            if not justification:
                self.waiver_findings.append(Finding(
                    "waiver-syntax", self.relpath, i,
                    "guberlint waiver without a justification — append "
                    "'-- <why this is safe>'"))
                continue
            for rule in rules:
                self.waivers.append(
                    Waiver(rule, i, file_scope, justification))

    def waived(self, rule: str, line: int) -> Optional[Waiver]:
        for w in self.waivers:
            if w.covers(rule, line):
                return w
        return None


class RepoIndex:
    """Lazy file index rules query. `root` is the repo checkout; rules
    address files by repo-relative path so a corpus test can point the
    same rule at a miniature fake repo (tests/test_lint_corpus.py)."""

    # python trees the AST rules walk (repo-relative)
    CODE_DIRS = ("gubernator_tpu", "scripts")

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self._files: Dict[str, Optional[SourceFile]] = {}

    # ------------------------------------------------------------ access

    def exists(self, relpath: str) -> bool:
        return os.path.exists(os.path.join(self.root, relpath))

    def get(self, relpath: str) -> Optional[SourceFile]:
        """SourceFile for `relpath`, or None when absent (corpus repos
        carry only the files their rule under test needs)."""
        if relpath not in self._files:
            if self.exists(relpath):
                self._files[relpath] = SourceFile(self.root, relpath)
            else:
                self._files[relpath] = None
        return self._files[relpath]

    def walk(self, subdir: str, suffix: str = ".py") -> List[str]:
        """Sorted repo-relative paths under `subdir` with `suffix`."""
        base = os.path.join(self.root, subdir)
        out: List[str] = []
        for dirpath, dirnames, filenames in os.walk(base):
            # lint_corpus holds the golden-violation corpus — miniature
            # fake repos full of DELIBERATE findings and malformed
            # waivers (tests/test_lint_corpus.py points rules at them
            # one root at a time); the real repo scan must never recurse
            # into it
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git", ".jax_cache",
                                        "lint_corpus")]
            for name in sorted(filenames):
                if name.endswith(suffix):
                    out.append(os.path.relpath(
                        os.path.join(dirpath, name), self.root))
        return sorted(out)

    def python_files(self) -> List[str]:
        """Every non-test python file the repo-wide rules scan."""
        out: List[str] = []
        for d in self.CODE_DIRS:
            if self.exists(d):
                out.extend(self.walk(d, ".py"))
        return out


class Rule:
    """Base class; subclasses set `id`/`doc` and implement check()."""

    id: str = ""
    doc: str = ""  # one-line invariant statement (rule catalogue)

    def check(self, repo: RepoIndex) -> Iterable[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, Rule] = {}


def register(rule_cls):
    """Class decorator: instantiate and register a Rule."""
    rule = rule_cls()
    if not rule.id:
        raise ValueError(f"{rule_cls.__name__} has no id")
    if rule.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    _REGISTRY[rule.id] = rule
    return rule_cls


def all_rules() -> Dict[str, Rule]:
    # import for side effect: rule modules self-register
    from gubernator_tpu.analysis import rules  # noqa: F401

    return dict(_REGISTRY)


def run(root: str, only: Sequence[str] = (),
        ) -> Tuple[List[Finding], List[Tuple[Finding, Waiver]]]:
    """Run rules against the checkout at `root`.

    Returns (findings, suppressed): `findings` is what gates CI;
    `suppressed` pairs each waived finding with its waiver so the corpus
    test can prove waivers actually suppress and operators can audit the
    waiver inventory (`--show-waived`).
    """
    repo = RepoIndex(root)
    rules = all_rules()
    if only:
        unknown = sorted(set(only) - set(rules))
        if unknown:
            raise ValueError(f"unknown rule id(s): {unknown}")
        rules = {k: v for k, v in rules.items() if k in only}

    findings: List[Finding] = []
    suppressed: List[Tuple[Finding, Waiver]] = []
    seen: set = set()  # several AST nodes can yield one logical finding
    for rule in rules.values():
        for f in rule.check(repo):
            if f in seen:
                continue
            seen.add(f)
            sf = repo.get(f.path)
            waiver = sf.waived(f.rule, f.line) if sf is not None else None
            if waiver is not None:
                suppressed.append((f, waiver))
            else:
                findings.append(f)
    # malformed waivers are findings regardless of which rules ran
    for relpath, sf in list(repo._files.items()):  # noqa: SLF001
        if sf is not None:
            findings.extend(sf.waiver_findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, suppressed


# --------------------------------------------------------------- helpers

def iter_lock_withs(tree: ast.AST):
    """Yield (With node, lock item expr) for every `with <lock>` scope.

    A with-item counts as a lock when its source rendering mentions
    'lock' — matches every discipline the repo uses: `with self._lock`,
    `with eng._lock`, `with lock:`, `with self._peer_lock`."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                src = ast.unparse(item.context_expr)
                if "lock" in src.lower():
                    yield node, item.context_expr
                    break


def node_lines(node: ast.AST) -> Tuple[int, int]:
    return node.lineno, getattr(node, "end_lineno", node.lineno)


def enclosing_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    """child -> parent map (ast has no parent pointers)."""
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents
