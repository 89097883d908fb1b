#!/usr/bin/env python
"""Render the markdown doc tree to HTML (reference parity: docs/Makefile +
Sphinx tree, /root/reference/docs/. Sphinx is not in this image, so this
uses the stdlib-adjacent `markdown` package — same role: a rendered,
navigable doc build from the committed sources).

Usage: python docs/build_docs.py [outdir]   (default docs/_build/html)
Or: make -C docs html
"""

from __future__ import annotations

import os
import sys

try:
    import markdown
except ImportError:  # minimal fallback: readable <pre> pages, no deps
    markdown = None

DOCS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(DOCS)

PAGES = [("index", os.path.join(ROOT, "README.md"), "Overview"),
         ("architecture", os.path.join(DOCS, "architecture.md"),
          "Architecture"),
         ("migration", os.path.join(DOCS, "migration.md"),
          "Migration from FlexFlow"),
         ("resilience", os.path.join(DOCS, "resilience.md"),
          "Fault tolerance & elastic recovery"),
         ("serving", os.path.join(DOCS, "serving.md"),
          "Serving (continuous batching, prefix cache, fleet router, "
          "quantized tier, disaggregated fleet + tiered cache, "
          "sampling + multi-tenant LoRA, rolling deployment, "
          "elastic fleet + preemption)"),
         ("performance", os.path.join(DOCS, "performance.md"),
          "Performance (host + in-graph overlap, Pallas kernel tier, "
          "search v2: persistent cost DB + multi-objective search)"),
         ("observability", os.path.join(DOCS, "observability.md"),
          "Observability (metrics registry, per-request tracing, "
          "Prometheus/JSON export)"),
         ("analysis", os.path.join(DOCS, "analysis.md"),
          "fflint static analysis (strategy passes + ffsan "
          "concurrency/trace-stability passes & runtime sanitizer)"),
         ("install", os.path.join(ROOT, "INSTALL.md"), "Install"),
         ("perf", os.path.join(ROOT, "PERF.md"),
          "PERF.md — what was measured on the chip, and how")]

TEMPLATE = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>{title} — flexflow_tpu</title>
<style>
body {{ font: 15px/1.5 system-ui, sans-serif; max-width: 60rem;
       margin: 2rem auto; padding: 0 1rem; color: #1a1a1a; }}
nav {{ border-bottom: 1px solid #ddd; padding-bottom: .5rem;
      margin-bottom: 1.5rem; }}
nav a {{ margin-right: 1rem; }}
pre {{ background: #f6f8fa; padding: .8rem; overflow-x: auto; }}
code {{ background: #f6f8fa; padding: .1rem .25rem; }}
table {{ border-collapse: collapse; }}
td, th {{ border: 1px solid #ccc; padding: .3rem .6rem; }}
</style></head><body>
<nav>{nav}</nav>
{body}
</body></html>
"""


def build(outdir: str) -> int:
    os.makedirs(outdir, exist_ok=True)
    nav = " ".join(f'<a href="{slug}.html">{title}</a>'
                   for slug, _, title in PAGES)
    n = 0
    for slug, path, title in PAGES:
        if not os.path.exists(path):
            print(f"skip {path} (missing)", file=sys.stderr)
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        if markdown is not None:
            body = markdown.markdown(
                text, extensions=["tables", "fenced_code"])
        else:
            import html

            body = f"<pre>{html.escape(text)}</pre>"
        with open(os.path.join(outdir, f"{slug}.html"), "w",
                  encoding="utf-8") as f:
            f.write(TEMPLATE.format(title=title, nav=nav, body=body))
        n += 1
    print(f"built {n} pages -> {outdir}")
    return 0


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(DOCS, "_build", "html")
    sys.exit(build(out))
