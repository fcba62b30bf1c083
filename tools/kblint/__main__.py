"""CLI: ``python -m tools.kblint [paths...] [--deep] [--list-rules]``.

Two tiers (docs/static_analysis.md):

- default: the syntactic per-file rules KB101–KB111 over ``paths``
- ``--deep``: additionally builds the whole-program call graph over
  ``kubebrain_tpu/ + tools/`` and runs the interprocedural
  rules KB112–KB122 plus the CFG/typestate leak rules KB123–KB126,
  filtered through tools/kblint/baseline.json and held to a wall-clock
  budget (CI fails if the analysis outgrows it).

``--sarif PATH`` additionally writes the run's findings as SARIF 2.1.0
for GitHub code scanning (baselined findings ride along marked
``unchanged``).

Both tiers share the content-hash cache in ``.kblint_cache/`` (disable
with ``KBLINT_CACHE=0``), so incremental runs only re-analyze edited
files.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import rules  # noqa: F401  -- importing registers the rules
from .cache import LintCache
from .core import (Baseline, DEEP_ROOTS, RULES, deep_analyze_paths,
                   lint_paths)

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")
DEFAULT_BUDGET = 60.0  # seconds: the stated CI wall-clock budget


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="kblint", description="kubebrain-tpu project-invariant linter"
    )
    parser.add_argument("paths", nargs="*", default=["kubebrain_tpu"],
                        help="files or directories to lint (syntactic tier)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalogue and exit")
    parser.add_argument("--root", default=os.getcwd(),
                        help="repo root for relative paths (default: cwd)")
    parser.add_argument("--deep", action="store_true",
                        help="run the interprocedural tier (KB112-KB122) "
                             "over kubebrain_tpu/ + tools/")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="baseline JSON pinning pre-existing deep "
                             "findings (default: tools/kblint/baseline.json)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="rewrite the baseline from the current deep "
                             "findings (preserves justifications)")
    parser.add_argument("--budget", type=float, default=DEFAULT_BUDGET,
                        help="wall-clock budget in seconds for the whole "
                             "run; exceeded = nonzero exit (default 60)")
    parser.add_argument("--lock-edges", default="",
                        help="JSON file of runtime lock-order edges "
                             "(util/lockcheck.py export) to cross-check "
                             "against the static KB115 graph; defaults to "
                             "$KBLINT_LOCK_EDGES on --deep runs")
    parser.add_argument("--lock-graph", action="store_true",
                        help="print the static lock-order graph and the "
                             "runtime cross-check report")
    parser.add_argument("--field-observed", default="",
                        help="JSON file of runtime field-guard observations "
                             "(util/fieldcheck.py export) to cross-check "
                             "against the static KB120 guard inference; "
                             "defaults to $KBLINT_FIELD_OBSERVED on --deep "
                             "runs")
    parser.add_argument("--field-guards", action="store_true",
                        help="print the static field-guard report and the "
                             "runtime fieldcheck cross-check")
    parser.add_argument("--leak-observed", default="",
                        help="JSON file of runtime leak observations "
                             "(util/leakcheck.py export) to cross-check "
                             "against the static KB123-KB126 obligation "
                             "sites; defaults to $KBLINT_LEAK_OBSERVED on "
                             "--deep runs")
    parser.add_argument("--leak-report", action="store_true",
                        help="print the static obligation-site report and "
                             "the runtime leakcheck cross-check")
    parser.add_argument("--sarif", default="",
                        help="write findings as SARIF 2.1.0 to this path "
                             "(for GitHub code-scanning upload)")
    parser.add_argument("--stats", action="store_true",
                        help="print resolution/propagation statistics")
    parser.add_argument("--no-cache", action="store_true",
                        help="bypass .kblint_cache/ for this run")
    args = parser.parse_args(argv)

    if args.list_rules:
        from .contexts import DEEP_RULES
        for rid in sorted(RULES):
            print(f"{rid}  {RULES[rid].summary}")
        for rid in sorted(DEEP_RULES):
            print(f"{rid}  {DEEP_RULES[rid]} [--deep]")
        return 0

    if not args.deep and (args.lock_edges or args.lock_graph or args.stats
                          or args.write_baseline or args.field_observed
                          or args.field_guards or args.leak_observed
                          or args.leak_report):
        # a typo'd CI line must not pass green while doing none of the work
        # (only EXPLICIT flags trigger this — the KBLINT_LOCK_EDGES /
        # KBLINT_FIELD_OBSERVED / KBLINT_LEAK_OBSERVED env fallbacks are
        # read later, on --deep runs only, so an exported env var cannot
        # fail an ordinary syntactic run). --sarif is fine without --deep:
        # a syntactic-only SARIF is still a complete scan of its tier.
        print("kblint: --lock-edges/--lock-graph/--field-observed/"
              "--field-guards/--leak-observed/--leak-report/--stats/"
              "--write-baseline require --deep", file=sys.stderr)
        return 2
    if args.deep and not args.lock_edges:
        args.lock_edges = os.environ.get("KBLINT_LOCK_EDGES", "")
    if args.deep and not args.field_observed:
        args.field_observed = os.environ.get("KBLINT_FIELD_OBSERVED", "")
    if args.deep and not args.leak_observed:
        args.leak_observed = os.environ.get("KBLINT_LEAK_OBSERVED", "")

    t0 = time.monotonic()
    cache = None if args.no_cache else LintCache.from_env(args.root)
    findings = lint_paths(args.paths or ["kubebrain_tpu"], root=args.root,
                          cache=cache)
    failed = False
    for f in findings:
        print(f.format())
    if findings:
        print(f"kblint: {len(findings)} finding(s)", file=sys.stderr)
        failed = True
    sarif_new = list(findings)
    sarif_pinned: list = []

    if args.deep:
        runtime_edges = None
        if args.lock_edges:
            try:
                with open(args.lock_edges, encoding="utf-8") as fh:
                    runtime_edges = [tuple(e) for e in
                                     json.load(fh).get("edges", [])]
            except (OSError, ValueError) as e:
                print(f"kblint: unreadable --lock-edges file: {e}",
                      file=sys.stderr)
                return 2
        field_obs = None
        if args.field_observed:
            try:
                with open(args.field_observed, encoding="utf-8") as fh:
                    data = json.load(fh)
                if not isinstance(data, dict):
                    raise ValueError(
                        "expected the export_observed() object form "
                        "({'fields': [...]}), got "
                        + type(data).__name__)
                field_obs = list(data.get("fields", []))
            except (OSError, ValueError) as e:
                print(f"kblint: unreadable --field-observed file: {e}",
                      file=sys.stderr)
                return 2
        leak_obs = None
        if args.leak_observed:
            try:
                with open(args.leak_observed, encoding="utf-8") as fh:
                    data = json.load(fh)
                if not isinstance(data, dict):
                    raise ValueError(
                        "expected the export_observed() object form "
                        "({'kinds': [...]}), got " + type(data).__name__)
                leak_obs = list(data.get("kinds", []))
            except (OSError, ValueError) as e:
                print(f"kblint: unreadable --leak-observed file: {e}",
                      file=sys.stderr)
                return 2
        result = deep_analyze_paths(args.root, DEEP_ROOTS, cache=cache,
                                    runtime_lock_edges=runtime_edges,
                                    runtime_field_obs=field_obs,
                                    runtime_leak_obs=leak_obs)
        baseline = Baseline.load(args.baseline)
        new, pinned, stale = baseline.split(result.findings)
        if args.write_baseline:
            Baseline.write(args.baseline, result.findings, previous=baseline)
            print(f"kblint-deep: wrote {len(result.findings)} finding(s) to "
                  f"{args.baseline}")
            new = []
        for f in new:
            print(f.format())
        if new:
            print(f"kblint-deep: {len(new)} non-baselined finding(s)",
                  file=sys.stderr)
            failed = True
        if stale and not args.write_baseline:  # the write just cleaned them
            print(f"kblint-deep: note: {len(stale)} stale baseline "
                  f"entr{'y' if len(stale) == 1 else 'ies'} no longer "
                  f"fire(s) — clean with --write-baseline", file=sys.stderr)
        s = result.stats
        print(f"kblint-deep: {s['files']} modules, {s['functions']} "
              f"functions, {s['resolved_calls']} calls resolved / "
              f"{s['unresolved_calls']} unresolved / {s['fn_refs']} fn-refs,"
              f" {len(pinned)} baselined, {s['lock_edges']} lock edges, "
              f"{s.get('leak_acquires', 0)} leak obligations, "
              f"{s['elapsed_seconds']}s")
        if args.stats:
            print(json.dumps(s, indent=1, sort_keys=True))
        if args.lock_graph:
            print(json.dumps(result.lock_graph, indent=1, sort_keys=True))
        if args.field_guards:
            print(json.dumps(result.field_guards, indent=1, sort_keys=True))
        if args.leak_report:
            print(json.dumps(result.leaks, indent=1, sort_keys=True))
        sarif_new.extend(new)
        sarif_pinned = list(pinned)

    if args.sarif:
        from .sarif import write_sarif
        write_sarif(args.sarif, sarif_new, sarif_pinned)
        print(f"kblint: wrote SARIF ({len(sarif_new)} result(s), "
              f"{len(sarif_pinned)} baselined) to {args.sarif}",
              file=sys.stderr)

    elapsed = time.monotonic() - t0
    if args.budget and elapsed > args.budget:
        print(f"kblint: BUDGET EXCEEDED: {elapsed:.1f}s > {args.budget:.0f}s"
              " — the analysis must stay inside the CI wall-clock budget",
              file=sys.stderr)
        return 2
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
