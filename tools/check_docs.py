#!/usr/bin/env python
"""Docs consistency checker (the CI docs lane).

Catches the failure mode PR 2 inherited: eight modules citing a
``DESIGN.md`` that did not exist in the repo.  Two rules:

1. Every relative markdown link ``[text](path)`` in a checked ``.md``
   file must resolve on disk (external ``http(s)://``/``mailto:``
   links and pure ``#anchor`` links are skipped).
2. Every ``*.md`` file referenced from checked source text — both
   ``docs/<name>.md`` paths (resolved from the repo root) and bare
   ``UPPERCASE.md`` citations like ``DESIGN.md`` (resolved from the
   repo root) — must exist.
3. Every ``core/batch_model.py``-style module citation in checked
   ``.md`` files must resolve — at the repo root, under ``src/`` or
   under ``src/repro/`` (docs conventionally drop the package prefix).
4. Every committed-artifact citation (``BENCH_<name>.json``, e.g. the
   perf-trajectory files ``benchmarks/run.py`` writes) must exist at
   the repo root.

Checked: ``src/``, ``tests/``, ``benchmarks/``, ``examples/``,
``tools/``, ``docs/``, ``README.md``, ``ROADMAP.md``.  Driver-owned /
historical files (ISSUE.md, CHANGES.md, PAPER*.md, SNIPPETS.md) are
not checked — they legitimately discuss files that never existed.

Exit 0 when clean; exit 1 and print one line per dangling reference.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

CHECKED_DIRS = ("src", "tests", "benchmarks", "examples", "tools", "docs")
CHECKED_ROOT_FILES = ("README.md", "ROADMAP.md")

_MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_DOC_PATH = re.compile(r"\bdocs/[\w.\-/]+\.md\b")
# a root-level doc named bare (``PAPER.md``); the same name ending a
# path (``a/b/NOTES.md``) is not a root citation
_BARE_CITE = re.compile(r"(?<![\w/.-])[A-Z][A-Z_]*\.md\b")
_MODULE_CITE = re.compile(
    r"\b((?:src/)?(?:repro/)?"
    r"(?:core|kernels|models|dist|launch|serving|reliability|configs|"
    r"ckpt|runtime|optim|data|tests|tools|benchmarks|examples)"
    r"/[\w./]*\.py)\b")
_ARTIFACT_CITE = re.compile(r"\bBENCH_\w+\.json\b")


def _checked_files(root: Path) -> list[Path]:
    files: list[Path] = []
    for d in CHECKED_DIRS:
        base = root / d
        if base.is_dir():
            files += sorted(p for p in base.rglob("*")
                            if p.suffix in (".py", ".md") and p.is_file())
    files += [root / f for f in CHECKED_ROOT_FILES if (root / f).is_file()]
    # the checker itself names the historical dangling file by design
    return [p for p in files if p.name != "check_docs.py"]


def check(root: Path) -> list[str]:
    errors: list[str] = []
    for path in _checked_files(root):
        text = path.read_text(encoding="utf-8", errors="replace")
        rel = path.relative_to(root)

        if path.suffix == ".md":
            for m in _MD_LINK.finditer(text):
                target = m.group(1).split("#", 1)[0]
                if not target or "://" in m.group(1) \
                        or m.group(1).startswith("mailto:"):
                    continue
                if not (path.parent / target).exists():
                    errors.append(f"{rel}: dangling link ({m.group(1)})")

        for m in _DOC_PATH.finditer(text):
            if not (root / m.group(0)).exists():
                errors.append(f"{rel}: dangling doc reference {m.group(0)}")
        for m in _BARE_CITE.finditer(text):
            if not (root / m.group(0)).exists():
                errors.append(f"{rel}: citation of missing {m.group(0)}")

        if path.suffix == ".md":
            for m in _MODULE_CITE.finditer(text):
                mod = m.group(1)
                if not any((root / pre / mod).exists()
                           for pre in ("", "src", "src/repro")):
                    errors.append(
                        f"{rel}: citation of missing module {mod}")
            for m in _ARTIFACT_CITE.finditer(text):
                if not (root / m.group(0)).exists():
                    errors.append(
                        f"{rel}: citation of missing artifact {m.group(0)}")
    return sorted(set(errors))


def main() -> int:
    root = Path(__file__).resolve().parent.parent
    errors = check(root)
    for e in errors:
        print(e)
    n = len(_checked_files(root))
    print(f"check_docs: {n} files checked, {len(errors)} dangling "
          f"reference(s)", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
