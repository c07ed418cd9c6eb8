#!/usr/bin/env python
"""Lint: every literally-named registry metric is Prometheus-legal AND
documented in docs/observability.md — and every documented metric
actually exists in code.

The metrics registry sanitizes names at registration, so an illegal
name silently mutates instead of failing — which means a dashboard
scraping the documented name would silently read nothing.  And a
metric that exists but is absent from docs/observability.md's metric
index is unfindable by the operator the observability layer exists
for.  This check closes both gaps statically:

* scan `analytics_zoo_tpu/` for
  ``.counter("name")`` / ``.gauge("name")`` / ``.histogram("name")``
  registrations whose first argument is a PLAIN string literal
  (f-strings and concatenations — the `span_<name>_seconds` /
  `events_<kind>_total` / `goodput_<clock>_<bucket>` families — are
  matched up to their literal prefix);
* each captured name must match the Prometheus metric-name grammar
  ``[a-zA-Z_:][a-zA-Z0-9_:]*``;
* each captured name (or family prefix) must appear verbatim in
  docs/observability.md.

And the REVERSE direction (`find_dead_doc_entries`): every backticked
metric name in the docs' metric-index table must still exist in the
source — verbatim, or (for ``family_<var>_suffix`` entries and
documented examples of such a family) via its literal prefix.  A
renamed-in-code metric would otherwise leave a dead doc entry that
operators would build dashboards on.

Run directly (`python scripts/check_metric_names.py`) or via the
tier-1 wrapper `tests/test_metric_names.py`.  Exit code 0 = clean.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "analytics_zoo_tpu")
DOCS = os.path.join(REPO, "docs", "observability.md")

#: `.counter("…")`, `.gauge('…')`, `.histogram("…")` with a plain
#: string literal (no f/r/b prefix — constructed names are matched by
#: their literal prefix via the same pattern when they start with one)
PATTERN = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"']([A-Za-z0-9_:]+)[\"']")

PROM_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _source_files():
    for dirpath, _dirnames, filenames in os.walk(PACKAGE):
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def find_violations():
    with open(DOCS, encoding="utf-8") as f:
        docs_text = f.read()
    violations = []
    for path in _source_files():
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for m in PATTERN.finditer(text):
            name = m.group(1)
            lineno = text.count("\n", 0, m.start()) + 1
            rel = os.path.relpath(path, REPO)
            if not PROM_NAME.match(name):
                violations.append(
                    (rel, lineno, name,
                     "not a legal Prometheus metric name"))
            elif name not in docs_text:
                violations.append(
                    (rel, lineno, name,
                     "missing from docs/observability.md's metric "
                     "index"))
    return violations


#: backticked tokens in the metric-index table that look like metric
#: names (families use `<var>` placeholders: `span_<name>_seconds`)
_DOC_TOKEN = re.compile(r"`([a-zA-Z_:][a-zA-Z0-9_:<>]*)`")


def _metric_index_rows(docs_text: str):
    """The `| metric | ... |` table rows of the '## Metric index'
    section (until the next section heading)."""
    in_section = False
    for line in docs_text.splitlines():
        if line.startswith("## "):
            in_section = line.strip() == "## Metric index"
            continue
        if in_section and line.lstrip().startswith("|"):
            yield line


def find_dead_doc_entries(docs_text=None, sources=None):
    """Reverse direction: metric-index entries with no counterpart in
    the source tree.  A token is alive when it appears verbatim in any
    scanned source file, when it is a `family_<var>` entry whose
    literal prefix appears, or when it is a documented example covered
    by some family's prefix."""
    if docs_text is None:
        with open(DOCS, encoding="utf-8") as f:
            docs_text = f.read()
    if sources is None:
        chunks = []
        for path in _source_files():
            with open(path, encoding="utf-8") as f:
                chunks.append(f.read())
        sources = "\n".join(chunks)
    tokens = []
    for row in _metric_index_rows(docs_text):
        cells = row.split("|")
        if len(cells) < 2:
            continue
        for tok in _DOC_TOKEN.findall(cells[1]):
            if tok not in ("metric",):      # the header row
                tokens.append(tok)
    family_prefixes = sorted(
        {t.split("<")[0] for t in tokens if "<" in t}
        | {t for t in tokens if t.endswith("_")})
    dead = []
    for tok in tokens:
        if "<" in tok:
            probe = tok.split("<")[0]
            if probe and probe in sources:
                continue
        elif tok in sources:
            continue
        elif any(p and tok.startswith(p) for p in family_prefixes):
            # a documented example of a computed-name family
            continue
        dead.append(tok)
    return dead


def main() -> int:
    violations = find_violations()
    dead = find_dead_doc_entries()
    if not violations and not dead:
        print("check_metric_names: clean")
        return 0
    if violations:
        print("check_metric_names: undocumented or illegal registry "
              "metric names:", file=sys.stderr)
        for path, lineno, name, why in violations:
            print(f"  {path}:{lineno}: {name!r} — {why}",
                  file=sys.stderr)
    if dead:
        print("check_metric_names: dead docs/observability.md metric-"
              "index entries (no counterpart in code):",
              file=sys.stderr)
        for tok in dead:
            print(f"  {tok!r}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
