#!/usr/bin/env python3
"""Docs consistency checker (stdlib only; run by the CI docs job).

Three invariants over README.md and docs/**/*.md:

1. every intra-repo markdown link ``[text](path)`` resolves to a real
   file or directory (fragments are stripped; http/mailto skipped);
2. every ``--flag`` mentioned in the prose exists in some argparse CLI of
   this repo — and when the surrounding line names a specific CLI
   (``live_train``, a ``benchmarks/*.py`` or ``examples/*.py`` path),
   the flag must exist in THAT file's parser;
3. every backticked CODE PATH (a `` `dir/file.ext` `` token with a slash,
   e.g. ``runtime/codec.py`` or ``../src/repro/runtime/codec.py``)
   resolves to a real file — relative to the doc, the repo root, or the
   ``src/repro`` package — so refactors can't silently orphan the spec's
   prose references the way they can't orphan its links.

Flags are discovered by scanning ``add_argument("--...")`` calls, so the
check needs no imports of repo code (and no JAX).

    python tools/check_docs.py          # exits non-zero on any violation
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
FLAG_RE = re.compile(r"(?<![-\w])(--[a-z][a-z0-9-]*)\b")
ADD_ARG_RE = re.compile(r"add_argument\(\s*[\"'](--[A-Za-z0-9-]+)[\"']")
# backticked path-like tokens: at least one '/', a known code/doc
# extension, no spaces — `runtime/codec.py`, `../src/.../net.py`, ...
CODE_REF_RE = re.compile(
    r"`([A-Za-z0-9_.\-]+(?:/[A-Za-z0-9_.\-]+)+"
    r"\.(?:py|json|md|yml|yaml|toml))`")

# flags that belong to tools outside this repo, not to our CLIs
EXTERNAL_FLAGS = {"--help"}

# code-ref roots tried after the doc's own dir: the repo root and the
# package dir (docs prose uses package-relative names like
# `runtime/live.py` for src/repro/runtime/live.py)
CODE_REF_ROOTS = (".", "src", "src/repro")

# substring of a doc line -> the CLI source file it refers to
CLI_HINTS = {
    "live_train": "src/repro/launch/live_train.py",
    "bench_live_throughput.py": "benchmarks/bench_live_throughput.py",
    "bench_fault_recovery.py": "benchmarks/bench_fault_recovery.py",
    "bench_replication.py": "benchmarks/bench_replication.py",
    "bench_dynamic_partition.py": "benchmarks/bench_dynamic_partition.py",
    "live_fault_tolerance.py": "examples/live_fault_tolerance.py",
    "live_tcp_fault_tolerance.py": "examples/live_tcp_fault_tolerance.py",
    "live_elastic_rejoin.py": "examples/live_elastic_rejoin.py",
    "live_compressed_wire.py": "examples/live_compressed_wire.py",
    "live_coordinator_failover.py": "examples/live_coordinator_failover.py",
    "fault_tolerance_demo.py": "examples/fault_tolerance_demo.py",
    "bench_wan_validation.py": "benchmarks/bench_wan_validation.py",
    "check_bench.py": "tools/check_bench.py",
    "chip_smoke.py": "chip_smoke.py",
}


def md_files() -> list[Path]:
    files = [REPO / "README.md"]
    files += sorted((REPO / "docs").rglob("*.md"))
    return [f for f in files if f.exists()]


def flags_of(py_path: Path) -> set[str]:
    try:
        return set(ADD_ARG_RE.findall(py_path.read_text(encoding="utf-8")))
    except OSError:
        return set()


def all_repo_flags() -> set[str]:
    flags: set[str] = set()
    for py in REPO.glob("*.py"):          # root-level scripts
        flags |= flags_of(py)
    for sub in ("src", "benchmarks", "examples", "tools"):
        for py in (REPO / sub).rglob("*.py"):
            flags |= flags_of(py)
    return flags


def check_links(md: Path) -> list[str]:
    errors = []
    for target in LINK_RE.findall(md.read_text(encoding="utf-8")):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = target.split("#", 1)[0]
        if not path:
            continue
        resolved = (md.parent / path).resolve()
        if not resolved.exists():
            errors.append(f"{md.relative_to(REPO)}: broken link -> {target}")
    return errors


def check_code_refs(md: Path) -> list[str]:
    """Invariant 3: backticked code paths resolve to real files."""
    errors = []
    for lineno, line in enumerate(
            md.read_text(encoding="utf-8").splitlines(), 1):
        for ref in CODE_REF_RE.findall(line):
            bases = [md.parent] + [REPO / r for r in CODE_REF_ROOTS]
            if not any((b / ref).resolve().exists() for b in bases):
                errors.append(f"{md.relative_to(REPO)}:{lineno}: code "
                              f"reference `{ref}` resolves to no file "
                              f"(tried doc dir, repo root, src, src/repro)")
    return errors


def check_flags(md: Path, union: set[str]) -> list[str]:
    errors = []
    for lineno, line in enumerate(
            md.read_text(encoding="utf-8").splitlines(), 1):
        found = [f for f in FLAG_RE.findall(line)
                 if f not in EXTERNAL_FLAGS]
        if not found:
            continue
        scoped = [cli for hint, cli in CLI_HINTS.items() if hint in line]
        for flag in found:
            if scoped:
                ok = any(flag in flags_of(REPO / cli) for cli in scoped)
                where = " or ".join(scoped)
            else:
                ok = flag in union
                where = "any repo CLI"
            if not ok:
                errors.append(f"{md.relative_to(REPO)}:{lineno}: "
                              f"flag {flag} not defined in {where}")
    return errors


def main() -> int:
    union = all_repo_flags()
    if not union:
        print("check_docs: found no argparse flags at all — "
              "is the repo layout intact?")
        return 2
    errors: list[str] = []
    files = md_files()
    for md in files:
        errors += check_links(md)
        errors += check_code_refs(md)
        errors += check_flags(md, union)
    if errors:
        print(f"check_docs: {len(errors)} problem(s):")
        for e in errors:
            print("  " + e)
        return 1
    print(f"check_docs: OK — {len(files)} markdown files, "
          f"{len(union)} known CLI flags")
    return 0


if __name__ == "__main__":
    sys.exit(main())
