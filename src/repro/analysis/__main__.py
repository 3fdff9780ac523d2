"""CLI entry point: ``python -m repro.analysis``.

Exit code 0 when every finding is baselined or suppressed; 1 when new
findings exist (this is what the CI gate keys on); 2 on usage errors.

Common invocations::

    python -m repro.analysis src/repro                 # full run
    python -m repro.analysis --format json             # CI gate
    python -m repro.analysis --changed-only            # fast local loop
    python -m repro.analysis --write-baseline src/repro  # accept current
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import List, Optional

from repro.analysis.engine import analyze_paths
from repro.analysis.findings import load_baseline, save_baseline

DEFAULT_BASELINE = "analysis/baseline.json"
DEFAULT_PATHS = ("src/repro",)


def _repo_root(start: Path) -> Path:
    cur = start.resolve()
    for cand in (cur, *cur.parents):
        if (cand / ".git").exists() or \
                (cand / DEFAULT_BASELINE).exists():
            return cand
    return start


def _changed_files(root: Path) -> Optional[List[Path]]:
    """Python files changed vs. HEAD (staged + unstaged + untracked)."""
    try:
        out = subprocess.run(
            ["git", "-C", str(root), "status", "--porcelain"],
            capture_output=True, text=True, check=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return None
    files: List[Path] = []
    for line in out.stdout.splitlines():
        name = line[3:].split(" -> ")[-1].strip().strip('"')
        p = root / name
        # untracked directories surface as one `?? dir/` entry — pass
        # them through whole; iter_python_files expands directories
        if (name.endswith(".py") or name.endswith("/")) and p.exists():
            files.append(p)
    return files


def _print_text(report) -> None:
    for f in report.new:
        print(f"{f.location()}: {f.rule} [new] {f.message}")
    if report.baselined:
        print(f"-- {len(report.baselined)} baselined finding(s) "
              "(see analysis/baseline.json)")
    if report.suppressed:
        print(f"-- {len(report.suppressed)} suppressed via "
              "# repro: noqa")
    print(f"{report.modules} module(s) analyzed, {len(report.new)} new, "
          f"{len(report.baselined)} baselined, "
          f"{len(report.suppressed)} suppressed")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="AST-based JAX/Pallas invariant linter "
                    "(rules RPR001-RPR006; see repro.analysis docs)")
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to analyze (default: "
                         f"{' '.join(DEFAULT_PATHS)} under the repo "
                         "root)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: {DEFAULT_BASELINE} "
                         "under the repo root; 'none' disables)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept all current findings into the "
                         "baseline file and exit 0")
    ap.add_argument("--changed-only", action="store_true",
                    help="analyze only files changed vs. git HEAD "
                         "(fast local loop; RPR006 parity checks run "
                         "only over the changed set)")
    ap.add_argument("--out", default=None,
                    help="write the (JSON) report to this file as well")
    ap.add_argument("--root", default=None,
                    help="repo root override (defaults to the nearest "
                         "ancestor with .git or the baseline file)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run "
                         "(e.g. RPR003,RPR006)")
    args = ap.parse_args(argv)

    root = Path(args.root).resolve() if args.root else \
        _repo_root(Path.cwd())

    if args.changed_only:
        changed = _changed_files(root)
        if changed is None:
            print("--changed-only: git unavailable, analyzing default "
                  "paths", file=sys.stderr)
            paths = [root / p for p in DEFAULT_PATHS]
        elif not changed:
            print("--changed-only: no changed python files")
            return 0
        else:
            paths = changed
    elif args.paths:
        paths = [Path(p) for p in args.paths]
    else:
        paths = [root / p for p in DEFAULT_PATHS]
    missing = [p for p in paths if not p.exists()]
    if missing:
        print(f"error: no such path(s): "
              f"{', '.join(str(p) for p in missing)}", file=sys.stderr)
        return 2

    rules = None
    if args.rules:
        from repro.analysis.rules import get_rules
        try:
            rules = get_rules(args.rules.split(","))
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    baseline_path = None
    accepted = set()
    if args.baseline != "none":
        baseline_path = (Path(args.baseline) if args.baseline
                         else root / DEFAULT_BASELINE)
        try:
            accepted = load_baseline(baseline_path)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    report = analyze_paths(paths, root=root, baseline=accepted,
                           rules=rules)

    if args.write_baseline:
        if baseline_path is None:
            print("error: --write-baseline with --baseline none",
                  file=sys.stderr)
            return 2
        baseline_path.parent.mkdir(parents=True, exist_ok=True)
        keep = [f for f in report.findings if not f.suppressed]
        save_baseline(baseline_path, keep)
        print(f"wrote {len(keep)} finding(s) to {baseline_path}")
        return 0

    if args.format == "json" or args.out:
        payload = json.dumps(report.to_dict(), indent=2)
        if args.format == "json":
            print(payload)
        if args.out:
            Path(args.out).write_text(payload + "\n")
    if args.format == "text":
        _print_text(report)

    return 1 if report.new else 0


if __name__ == "__main__":
    sys.exit(main())
