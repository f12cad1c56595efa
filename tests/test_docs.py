"""Docs-drift tests: the flag sets in README.md / docs/*.md and the
``repro.launch.serve`` argparser must not diverge.

Two directions:

* every ``--flag`` token the docs mention (minus a small allowlist of
  flags that belong to OTHER tools, e.g. benchmarks/run.py) must exist in
  the serve argparser — docs cannot reference removed/renamed flags;
* every serve argparser flag (minus ``--help``) must be mentioned in at
  least one of the docs — new flags cannot ship undocumented.

Plus structural checks that the documented entry points / bench artifacts
the docs point at actually exist.
"""
import json
import re
from pathlib import Path

import pytest

from repro.launch.serve import build_parser

pytestmark = pytest.mark.docs

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md", "docs/serving.md", "docs/kernels.md",
        "docs/observability.md"]

# flags mentioned in the docs that belong to other CLIs, not serve.py
FOREIGN_FLAGS = {
    "--sections",       # benchmarks/run.py
    "--four-chips",     # chip_smoke.py
    "--xla",            # --xla_force_host_platform_device_count: an
                        # XLA_FLAGS value (the --tp docs), not a CLI flag
}
# serve.py flags exempt from the must-be-documented rule
UNDOCUMENTED_OK = {
    "--help",           # argparse built-in
}

FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")


def _doc_text(name):
    path = REPO / name
    assert path.exists(), f"documented file {name} is missing"
    return path.read_text()


def _doc_flags():
    flags = {}
    for name in DOCS:
        for flag in FLAG_RE.findall(_doc_text(name)):
            flags.setdefault(flag, set()).add(name)
    return flags


def _serve_flags():
    return {opt for action in build_parser()._actions
            for opt in action.option_strings if opt.startswith("--")}


def test_doc_flags_exist_in_serve_parser():
    """Docs may only reference serve flags that actually exist."""
    serve = _serve_flags()
    unknown = {f: sorted(where)
               for f, where in _doc_flags().items()
               if f not in serve and f not in FOREIGN_FLAGS}
    assert not unknown, (
        f"docs mention flags the serve argparser does not define: "
        f"{unknown} — fix the doc, or add the flag to FOREIGN_FLAGS if it "
        f"belongs to another tool")


def test_serve_flags_are_documented():
    """Every serve flag must appear in README.md or docs/ (add genuinely
    internal/debug flags to UNDOCUMENTED_OK — deliberately)."""
    documented = set(_doc_flags())
    missing = sorted(_serve_flags() - documented - UNDOCUMENTED_OK)
    assert not missing, (
        f"serve flags missing from README.md/docs: {missing} — document "
        f"them (docs/serving.md has the flag reference table)")


def test_foreign_flags_are_actually_foreign():
    """The allowlist must not mask real serve flags."""
    overlap = sorted(FOREIGN_FLAGS & _serve_flags())
    assert not overlap, f"FOREIGN_FLAGS shadow real serve flags: {overlap}"


def test_docs_exist_and_crosslink():
    readme = _doc_text("README.md")
    assert "docs/serving.md" in readme and "docs/kernels.md" in readme
    assert "scripts/tier1.sh" in readme, "README must name the tier-1 command"


def test_bench_rows_named_in_kernel_docs_exist():
    """docs/kernels.md references BENCH_kernels.json rows by name; those
    rows must exist (section map cannot rot)."""
    rows = {r["name"] for r in json.loads(_doc_text("BENCH_kernels.json"))}
    text = _doc_text("docs/kernels.md")
    # every backticked token shaped like a bench row name must be one
    bench_like = {n for n in re.findall(r"`([a-z0-9_]+)`", text)
                  if re.search(r"_(b\d+|\d+x\d+|k\d+|s\d+)", n)}
    missing = sorted(bench_like - rows)
    assert not missing, (
        f"docs/kernels.md references BENCH_kernels.json rows that do not "
        f"exist: {missing}")


def test_serving_docs_name_real_stats_fields():
    """The ServeStats glossary in docs/serving.md must list exactly the
    dataclass's fields."""
    from repro.runtime import ServeStats
    import dataclasses
    text = _doc_text("docs/serving.md")
    fields = {f.name for f in dataclasses.fields(ServeStats)}
    # table rows look like: | `field` | ...
    documented = set(re.findall(r"\|\s*`([a-z_]+)`(?:,\s*`([a-z_]+)`)?",
                                text))
    documented = {n for pair in documented for n in pair if n}
    missing = sorted(fields - documented)
    assert not missing, (
        f"ServeStats fields missing from the docs/serving.md glossary: "
        f"{missing}")
