#!/usr/bin/env python
"""graftlint CLI — JAX/TPU-aware static analysis over the repo.

Usage:
    python scripts/graftlint.py                   # default scope (below)
    python scripts/graftlint.py --changed         # pre-commit: lint only
                                                  # files in git diff
    python scripts/graftlint.py --since main      # lint files changed
                                                  # since a ref
    python scripts/graftlint.py --json paddle_tpu
    python scripts/graftlint.py --sarif paddle_tpu/serving
    python scripts/graftlint.py --rule use-after-donate paddle_tpu
    python scripts/graftlint.py --list-rules
    python scripts/graftlint.py --manifest        # graftprog program
                                                  # manifest (JSON)
    python scripts/graftlint.py --memory          # graftmem HBM capacity
                                                  # manifest (JSON)
    python scripts/graftlint.py --comm            # graftcomm cross-host
                                                  # seam manifest (JSON)

Default scope is the library AND the perf-critical entrypoints:
``paddle_tpu/``, ``__graft_entry__.py``, ``scripts/``.
With ``--changed``/``--since`` the whole default scope is still PARSED
(the project index needs it — interprocedural rules resolve cross-file),
but only the changed files are linted; the on-disk parse cache under
``.graftlint_cache/`` keeps that fast (``--no-cache`` bypasses it).

Exit code 0 iff there are zero unsuppressed findings (the CI contract —
tests/test_static_analysis.py pins this over the default scope).
"""

import argparse
import importlib.util
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the library plus every perf-critical entrypoint the gate covers
DEFAULT_SCOPE = ("paddle_tpu", "__graft_entry__.py", "scripts")
CACHE_PATH = os.path.join(ROOT, ".graftlint_cache", "parse.pkl")


def _load_analysis():
    """Load paddle_tpu/tools/analysis WITHOUT importing the paddle_tpu
    package: ``import paddle_tpu.tools.analysis`` would execute the whole
    framework __init__ (jax included), so a broken tree — exactly what a
    linter must be able to diagnose — would crash the linter itself.  The
    analysis package is pure relative imports, so it loads standalone."""
    pkg_dir = os.path.join(ROOT, "paddle_tpu", "tools", "analysis")
    spec = importlib.util.spec_from_file_location(
        "graftlint_analysis", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["graftlint_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


_analysis = _load_analysis()
default_checkers = _analysis.default_checkers
format_json = _analysis.format_json
format_sarif = _analysis.format_sarif
format_text = _analysis.format_text
run_analysis = _analysis.run_analysis


def _changed_files(since):
    """Repo-relative .py paths from ``git diff --name-only <since>``
    (default HEAD — staged AND unstaged), plus untracked .py files.
    Linting reads the ON-DISK content of those files, so an unstaged fix
    can mask a staged violation; the full-scope CI gate is the
    authority."""
    out = []
    cmds = [["git", "diff", "--name-only", since or "HEAD"],
            ["git", "ls-files", "--others", "--exclude-standard"]]
    for cmd in cmds:
        try:
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True, timeout=60, check=True)
        except (OSError, subprocess.SubprocessError) as e:
            print(f"graftlint: cannot determine changed files "
                  f"({' '.join(cmd)}: {e})", file=sys.stderr)
            return None
        out.extend(line.strip() for line in proc.stdout.splitlines()
                   if line.strip())
    scope_files = {p for p in DEFAULT_SCOPE
                   if not os.path.isdir(os.path.join(ROOT, p))}
    scope_dirs = tuple(p + "/" for p in DEFAULT_SCOPE
                       if os.path.isdir(os.path.join(ROOT, p)))
    keep = []
    for rel in sorted(set(out)):
        if not rel.endswith(".py"):
            continue
        if rel not in scope_files and not rel.startswith(scope_dirs):
            continue
        full = os.path.join(ROOT, rel)
        if os.path.exists(full):     # deleted files have nothing to lint
            keep.append(full)
    return keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graftlint", description=__doc__)
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to scan "
                         f"(default: {' '.join(DEFAULT_SCOPE)})")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--sarif", action="store_true",
                    help="SARIF 2.1.0 output (CI annotators)")
    ap.add_argument("--rule", action="append", dest="rules", default=None,
                    metavar="RULE", help="run only the named rule(s)")
    ap.add_argument("--changed", action="store_true",
                    help="lint only files in git diff (+ untracked); the "
                         "project index still covers the whole scope")
    ap.add_argument("--since", metavar="REF", default=None,
                    help="with/without --changed: lint files changed "
                         "since REF (git diff REF)")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the on-disk parse cache")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="also list suppressed findings")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    ap.add_argument("--manifest", action="store_true",
                    help="emit the graftprog compile-surface manifest "
                         "(deterministic JSON) over the default scope "
                         "and exit")
    ap.add_argument("--memory", action="store_true", dest="memory",
                    help="emit the graftmem HBM capacity manifest "
                         "(deterministic JSON) over the default scope "
                         "and exit")
    ap.add_argument("--comm", action="store_true", dest="comm",
                    help="emit the graftcomm cross-host seam manifest "
                         "(deterministic JSON) over the default scope "
                         "and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for c in default_checkers():
            doc = (sys.modules[type(c).__module__].__doc__ or "").strip()
            first = doc.splitlines()[0] if doc else ""
            print(f"{c.name:20s} [{c.severity}] {first}")
        return 0

    scope = [os.path.join(ROOT, p) for p in DEFAULT_SCOPE]
    project_paths = scope
    if args.manifest:
        if args.changed or args.since or args.paths:
            ap.error("--manifest walks the whole default scope; it "
                     "cannot be combined with --changed/--since/paths")
        cache = None if args.no_cache else CACHE_PATH
        manifest = _analysis.build_manifest_for_paths(
            scope, root=ROOT, cache_path=cache)
        print(_analysis.format_manifest(manifest))
        return 0
    if args.memory:
        if args.changed or args.since or args.paths:
            ap.error("--memory walks the whole default scope; it "
                     "cannot be combined with --changed/--since/paths")
        cache = None if args.no_cache else CACHE_PATH
        manifest = _analysis.build_memory_manifest_for_paths(
            scope, root=ROOT, cache_path=cache)
        print(_analysis.format_manifest(manifest))
        return 0
    if args.comm:
        if args.changed or args.since or args.paths:
            ap.error("--comm walks the whole default scope; it "
                     "cannot be combined with --changed/--since/paths")
        cache = None if args.no_cache else CACHE_PATH
        manifest = _analysis.build_comm_manifest_for_paths(
            scope, root=ROOT, cache_path=cache)
        print(_analysis.format_manifest(manifest))
        return 0
    if args.changed or args.since:
        if args.paths:
            ap.error("--changed/--since lint the git working set; they "
                     "cannot be combined with explicit paths")
        paths = _changed_files(args.since)
        if paths is None:
            return 2
        if not paths:
            print("graftlint: no changed python files in scope")
            return 0
    elif args.paths:
        paths = [p if os.path.isabs(p) else os.path.join(ROOT, p)
                 for p in args.paths]
    else:
        paths = scope

    cache = None if args.no_cache else CACHE_PATH
    result = run_analysis(paths, root=ROOT, rules=args.rules,
                          project_paths=project_paths, cache_path=cache)
    if args.sarif:
        print(format_sarif(result, checkers=default_checkers()))
    elif args.as_json:
        print(format_json(result))
    else:
        print(format_text(result, verbose=args.verbose))
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
