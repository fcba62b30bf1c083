"""Rule registry, suppression handling, baselines, and the lint drivers
(syntactic per-file tier + the interprocedural deep tier)."""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import time
from typing import Any, Iterable


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule_id: str
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule_id} {self.message}"


class Rule:
    """One project invariant. Subclasses set ``rule_id``/``summary`` and
    implement ``check(tree, src)`` yielding ``(node, message)`` pairs."""

    rule_id: str = ""
    summary: str = ""

    def applies(self, relpath: str) -> bool:
        """Whether this rule runs on the file at repo-relative ``relpath``."""
        return True

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        raise NotImplementedError


RULES: dict[str, Rule] = {}


def register(rule_cls: type[Rule]) -> type[Rule]:
    rule = rule_cls()
    if not rule.rule_id:
        raise ValueError(f"{rule_cls.__name__} has no rule_id")
    if rule.rule_id in RULES:
        raise ValueError(f"duplicate rule id {rule.rule_id}")
    RULES[rule.rule_id] = rule
    return rule_cls


_DISABLE_RE = re.compile(r"#\s*kblint:\s*disable=([A-Z0-9,\s]+?)(?:\s*--.*)?$")
_DISABLE_FILE_RE = re.compile(r"#\s*kblint:\s*disable-file=([A-Z0-9,\s]+?)(?:\s*--.*)?$")


def _disabled_on_line(line: str) -> set[str]:
    m = _DISABLE_RE.search(line)
    if not m:
        return set()
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


def _file_disabled(lines: list[str]) -> set[str]:
    out: set[str] = set()
    for line in lines[:20]:  # file-level pragmas live in the header
        m = _DISABLE_FILE_RE.search(line)
        if m:
            out |= {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _suppression_lines(node: ast.AST, tree: ast.Module) -> set[int]:
    """Lines whose disable comment covers ``node``: the node's own first
    line, the comment line directly above it, plus the header line of every
    enclosing with/def/async-def block (so one pragma on ``with
    self._lock:`` covers the whole block)."""
    covered = {getattr(node, "lineno", 0)}
    target_line = getattr(node, "lineno", 0)
    for parent in ast.walk(tree):
        if not isinstance(parent, (ast.With, ast.AsyncWith,
                                   ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        end = getattr(parent, "end_lineno", 0) or 0
        if parent.lineno <= target_line <= end:
            covered.add(parent.lineno)
    return covered


def lint_source(src: str, relpath: str, rules: Iterable[Rule] | None = None) -> list[Finding]:
    rules = list(rules if rules is not None else RULES.values())
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(relpath, e.lineno or 0, e.offset or 0, "KB000",
                        f"syntax error: {e.msg}")]
    lines = src.splitlines()
    file_off = _file_disabled(lines)
    findings: list[Finding] = []
    for rule in rules:
        if rule.rule_id in file_off or not rule.applies(relpath):
            continue
        for node, message in rule.check(tree, src):
            line = getattr(node, "lineno", 0)
            col = getattr(node, "col_offset", 0)
            candidates = _suppression_lines(node, tree)
            # a pure comment line directly above the finding also counts
            if line >= 2 and lines[line - 2].lstrip().startswith("#"):
                candidates.add(line - 1)
            suppressed = any(
                rule.rule_id in _disabled_on_line(lines[ln - 1])
                for ln in candidates if 1 <= ln <= len(lines)
            )
            if not suppressed:
                findings.append(Finding(relpath, line, col, rule.rule_id, message))
    findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
    return findings


def iter_py_files(paths: list[str], root: str) -> Iterable[str]:
    skip_dirs = {".git", "__pycache__", ".claude", "node_modules"}
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap) and ap.endswith(".py"):
            yield ap
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = [d for d in dirnames if d not in skip_dirs]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def lint_paths(paths: list[str], root: str | None = None,
               cache: "Any | None" = None) -> list[Finding]:
    """Syntactic tier over files/dirs; ``cache`` (a LintCache) makes the
    sweep incremental — unchanged files replay their cached findings."""
    root = root or os.getcwd()
    findings: list[Finding] = []
    for ap in iter_py_files(paths, root):
        relpath = os.path.relpath(ap, root)
        try:
            with open(ap, encoding="utf-8") as f:
                src = f.read()
        except (OSError, UnicodeDecodeError) as e:
            findings.append(Finding(relpath, 0, 0, "KB000", f"unreadable: {e}"))
            continue
        entry = cache.get(relpath, src) if cache is not None else None
        if entry is not None and "findings" in entry:
            findings.extend(
                Finding(relpath, f[0], f[1], f[2], f[3])
                for f in entry["findings"])
            continue
        file_findings = lint_source(src, relpath)
        if cache is not None:
            new_entry = dict(entry or {})
            new_entry["findings"] = [
                [f.line, f.col, f.rule_id, f.message] for f in file_findings]
            cache.put(relpath, src, new_entry)
        findings.extend(file_findings)
    return findings


# ------------------------------------------------------------------ baseline

_LINE_REF_RE = re.compile(r":\d+|\bline \d+")


def normalize_message(msg: str) -> str:
    """Baseline matching key: line numbers inside messages drift with
    unrelated edits, so they are masked out of the identity — both the
    ``path.py:NN`` form and KB114's ``at line NN`` form."""
    return _LINE_REF_RE.sub(":N", msg)


class Baseline:
    """Pinned pre-existing findings (tools/kblint/baseline.json).

    A baseline entry matches on (rule, path, normalized message) — NOT on
    the line number, which moves under unrelated edits. Baselined findings
    are reported as counts, not failures; entries that no longer fire are
    listed as stale so they get cleaned out rather than silently masking a
    future regression at the same spot."""

    def __init__(self, entries: list[dict], path: str | None = None) -> None:
        self.entries = entries
        self.path = path
        self._keys = {self._entry_key(e) for e in entries}

    @staticmethod
    def _entry_key(e: dict) -> tuple[str, str, str]:
        return (e["rule"], e["path"], normalize_message(e["message"]))

    @staticmethod
    def _finding_key(f: Finding) -> tuple[str, str, str]:
        return (f.rule_id, f.path, normalize_message(f.message))

    @classmethod
    def load(cls, path: str) -> "Baseline":
        try:
            with open(path, encoding="utf-8") as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            return cls([], path)
        return cls(list(data.get("findings", [])), path)

    def split(self, findings: list[Finding]
              ) -> tuple[list[Finding], list[Finding], list[dict]]:
        """(new findings, baselined findings, stale baseline entries)."""
        new: list[Finding] = []
        pinned: list[Finding] = []
        fired: set[tuple[str, str, str]] = set()
        for f in findings:
            key = self._finding_key(f)
            if key in self._keys:
                pinned.append(f)
                fired.add(key)
            else:
                new.append(f)
        stale = [e for e in self.entries if self._entry_key(e) not in fired]
        return new, pinned, stale

    @classmethod
    def write(cls, path: str, findings: list[Finding],
              previous: "Baseline | None" = None) -> None:
        """Rewrite the baseline from the current findings, preserving the
        human justification of entries that keep firing."""
        whys: dict[tuple[str, str, str], str] = {}
        if previous is not None:
            for e in previous.entries:
                if e.get("why"):
                    whys[cls._entry_key(e)] = e["why"]
        entries = []
        seen: set[tuple[str, str, str]] = set()
        for f in sorted(findings, key=lambda f: (f.rule_id, f.path, f.line)):
            key = cls._finding_key(f)
            if key in seen:
                continue
            seen.add(key)
            entries.append({
                "rule": f.rule_id, "path": f.path, "line": f.line,
                "message": f.message,
                "why": whys.get(key, "TODO: justify or fix"),
            })
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({
                "version": 1,
                "note": ("Pinned pre-existing deep findings. Entries match "
                         "on (rule, path, message-with-line-numbers-masked);"
                         " fix the code or justify in 'why'. Regenerate with"
                         " python -m tools.kblint --deep --write-baseline."),
                "findings": entries,
            }, fh, indent=1)
            fh.write("\n")


# ---------------------------------------------------------------- deep tier

#: the deep tier's call-graph universe (relative to the repo root); the
#: syntactic tier keeps whatever paths the caller passes (tests included),
#: but tests are deliberately NOT in the call graph — fixture code full of
#: deliberate violations would drown the serving-path signal
DEEP_ROOTS = ["kubebrain_tpu", "tools"]


def deep_analyze_sources(sources: dict[str, str],
                         runtime_lock_edges: list | None = None,
                         runtime_field_obs: list | None = None,
                         runtime_leak_obs: list | None = None) -> Any:
    """Deep tier over in-memory {relpath: source} (the self-test entry):
    build summaries, stitch the graph, propagate, run KB112–KB126."""
    from .contexts import analyze
    from .graph import ProjectGraph, extract_module
    summaries = [extract_module(src, rp) for rp, src in sorted(sources.items())]
    graph = ProjectGraph(summaries)
    # [] is real data ("a run that nested nothing"), distinct from None
    # ("no runtime export supplied") — collapsing them would mask a
    # zero-coverage detector as "no data"
    edges = ([tuple(e) for e in runtime_lock_edges]
             if runtime_lock_edges is not None else None)
    return analyze(graph, runtime_lock_edges=edges,
                   runtime_field_obs=runtime_field_obs,
                   sources=dict(sources), runtime_leak_obs=runtime_leak_obs)


def deep_analyze_paths(root: str, roots: list[str] | None = None,
                       cache: "Any | None" = None,
                       runtime_lock_edges: list | None = None,
                       runtime_field_obs: list | None = None,
                       runtime_leak_obs: list | None = None) -> Any:
    """Deep tier over the repo tree. Per-file extraction rides the same
    content-hash cache as the syntactic tier (entry key "summary"). The
    sources read here are handed on to the CFG tier, which re-lowers the
    few files hosting acquire sites (cheap next to extraction)."""
    from .contexts import analyze
    from .graph import ModuleSummary, ProjectGraph, extract_module
    t0 = time.monotonic()
    summaries: list[ModuleSummary] = []
    sources: dict[str, str] = {}
    parsed = from_cache = 0
    for ap in iter_py_files(roots or DEEP_ROOTS, root):
        relpath = os.path.relpath(ap, root).replace("\\", "/")
        try:
            with open(ap, encoding="utf-8") as f:
                src = f.read()
        except (OSError, UnicodeDecodeError):
            continue
        sources[relpath] = src
        entry = cache.get(relpath, src) if cache is not None else None
        if entry is not None and "summary" in entry:
            summaries.append(ModuleSummary.from_dict(entry["summary"]))
            from_cache += 1
            continue
        ms = extract_module(src, relpath)
        parsed += 1
        if cache is not None:
            new_entry = dict(entry or {})
            new_entry["summary"] = ms.to_dict()
            # keep the syntactic findings alongside so one entry serves
            # both tiers
            if "findings" not in new_entry:
                new_entry["findings"] = [
                    [f.line, f.col, f.rule_id, f.message]
                    for f in lint_source(src, relpath)]
            cache.put(relpath, src, new_entry)
        summaries.append(ms)
    graph = ProjectGraph(summaries)
    edges = ([tuple(e) for e in runtime_lock_edges]
             if runtime_lock_edges is not None else None)
    result = analyze(graph, runtime_lock_edges=edges,
                     runtime_field_obs=runtime_field_obs,
                     sources=sources, runtime_leak_obs=runtime_leak_obs)
    result.stats["files_parsed"] = parsed
    result.stats["files_from_cache"] = from_cache
    result.stats["elapsed_seconds"] = round(time.monotonic() - t0, 3)
    return result
