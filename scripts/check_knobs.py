"""Static gate: the README knob index and the engine's ``PATHWAY_*`` env
reads stay in sync — in BOTH directions.

- read→doc: every knob the engine reads (``os.environ.get(...)``,
  ``os.environ[...]``, the ``_env_*`` helpers of ``internals/config.py``)
  must be documented in README.md. A knob cannot ship without an
  operator-facing description.
- doc→read: every knob README documents must still be referenced
  somewhere in the codebase. A knob that survives in the README after
  its last read site was refactored away is a stale trap — an operator
  sets it and nothing happens.

Rides the shared AST-gate framework (``pathway_tpu/analysis/astgate.py``)
and registers as the ``knobs`` gate for ``scripts/check_all.py``.
Usable standalone: ``python scripts/check_knobs.py`` → exit 0/1.
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from pathway_tpu.analysis import astgate  # noqa: E402

#: read sites; \s* spans newlines so black-wrapped calls still match
_READ = re.compile(
    r"(?:os\.environ\.get\(|os\.environ\[|environ\.get\(|getenv\(|"
    r"_env_(?:bool|int|float|addresses|f|i)\()\s*[\"'](PATHWAY_[A-Z0-9_]+)[\"']",
    re.S,
)

#: any knob-shaped token (documentation side + reference scan)
_KNOB = re.compile(r"(?<![A-Z0-9_])(PATHWAY_[A-Z0-9_]+)(?![A-Z0-9_])")

#: code trees scanned for "is this documented knob still referenced"
_REFERENCE_ROOTS = ("pathway_tpu", "scripts", "tests")
_REFERENCE_FILES = ("__graft_entry__.py",)


def collect_knobs(package_dir: str | None = None) -> dict[str, list[str]]:
    """knob name -> files reading it, across the whole package."""
    package_dir = package_dir or astgate.PACKAGE_DIR
    knobs: dict[str, list[str]] = {}
    for path in astgate.iter_py_files(package_dir):
        text = astgate.read_text(path)
        for m in _READ.finditer(text):
            knobs.setdefault(m.group(1), []).append(
                os.path.relpath(path, ROOT)
            )
    return knobs


def undocumented(readme_path: str | None = None) -> dict[str, list[str]]:
    """Knobs read by the engine but absent from README.md. Matching is
    whole-name (a documented ``PATHWAY_TRACE_FILE`` must not vouch for an
    undocumented ``PATHWAY_TRACE`` substring-knob, or vice versa)."""
    readme_path = readme_path or os.path.join(ROOT, "README.md")
    with open(readme_path, encoding="utf-8") as f:
        readme = f.read()
    return {
        k: sorted(set(files))
        for k, files in collect_knobs().items()
        if not re.search(rf"(?<![A-Z0-9_]){re.escape(k)}(?![A-Z0-9_])", readme)
    }


def documented_knobs(readme_path: str | None = None) -> set[str]:
    readme_path = readme_path or os.path.join(ROOT, "README.md")
    with open(readme_path, encoding="utf-8") as f:
        return set(_KNOB.findall(f.read()))


def referenced_knobs() -> set[str]:
    """Every knob-shaped token appearing anywhere in the codebase (reads,
    writes, child-env stamping, tests) — the liveness evidence for the
    doc→read direction."""
    out: set[str] = set()
    roots = [os.path.join(ROOT, r) for r in _REFERENCE_ROOTS]
    files = [os.path.join(ROOT, f) for f in _REFERENCE_FILES]
    for root in roots:
        files.extend(astgate.iter_py_files(root))
    for path in files:
        if not os.path.exists(path):
            continue
        out |= set(_KNOB.findall(astgate.read_text(path)))
    return out


def stale_documented(readme_path: str | None = None) -> set[str]:
    """Knobs the README documents that nothing in the codebase references
    anymore — setting them is a silent no-op. Wildcard family mentions
    (``PATHWAY_SINK_BREAKER_*`` renders as a trailing-underscore token)
    are prose, not knob rows."""
    docs = {
        k for k in documented_knobs(readme_path) if not k.endswith("_")
    }
    return docs - referenced_knobs()


@astgate.gate(
    "knobs",
    "every PATHWAY_* env read is documented in README and every "
    "documented knob is still referenced somewhere",
)
def knobs_gate() -> list[str]:
    problems: list[str] = []
    for k, files in sorted(undocumented().items()):
        problems.append(
            f"{k} read in {', '.join(files)} but undocumented — add it to "
            "the README knob index"
        )
    for k in sorted(stale_documented()):
        problems.append(
            f"{k} documented in README but referenced nowhere in the "
            "codebase — stale doc (drop the row, or restore the read)"
        )
    return problems


def main() -> int:
    problems = knobs_gate()
    if problems:
        print("check_knobs FAILED:", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 1
    n = len(collect_knobs())
    print(f"check_knobs OK ({n} knobs, documented and live both ways)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
