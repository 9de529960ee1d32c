"""Render a ``repro.bench`` result document to markdown (docs/RESULTS.md).

The numbers the documentation cites are generated, never hand-written: the
renderer stamps the suite, config and environment the document was produced
with, so a stale RESULTS.md is detectable from its own header.
"""
from __future__ import annotations

import time


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, float):
        return f"{v:.4g}"
    if v is None:
        return "—"
    return str(v)


def _md_table(columns: list, rows: list) -> list:
    out = ["| " + " | ".join(str(c) for c in columns) + " |",
           "|" + "---|" * len(columns)]
    for r in rows:
        out.append("| " + " | ".join(_fmt(r.get(c)) for c in columns) + " |")
    return out


def _render_sweep(exp: dict) -> list:
    lines = []
    x = exp["x"]
    # series-column header: lock sweeps by default, overridable per
    # experiment (the serve suite's series are admission policies)
    slabel = exp.get("meta", {}).get("series_label", "lock")
    xs: list = []
    for s in exp["series"]:
        for p in s["points"]:
            if p[x] not in xs:
                xs.append(p[x])
    xs.sort()
    # one table per metric: rows = series, columns = x values
    metrics = [m for m in exp.get("y", []) if m not in ("episodes", "wall_s")]
    primary = [m for m in ("throughput", "latency") if m in metrics]
    rest = [m for m in metrics if m not in primary]
    for metric in primary + rest:
        lines.append(f"**{metric}** vs {x}:")
        lines.append("")
        cols = [slabel] + [str(v) for v in xs]
        rows = []
        for s in exp["series"]:
            by_x = {p[x]: p.get(metric) for p in s["points"]}
            row = {slabel: s["label"]}
            row.update({str(v): by_x.get(v) for v in xs})
            rows.append(row)
        lines += _md_table(cols, rows)
        lines.append("")
    return lines


def _render_table(exp: dict) -> list:
    if not exp["rows"]:
        return ["*(no rows — source artifacts not present for this run)*",
                ""]
    note = exp.get("meta", {}).get("note")
    head = [note, ""] if note else []
    return head + _md_table(exp["columns"], exp["rows"]) + [""]


def _render_scalars(exp: dict) -> list:
    lines = []
    for k, v in exp["values"].items():
        lines.append(f"- **{k}**: {_fmt(v)}")
    lines.append("")
    return lines


def _render_hist(exp: dict) -> list:
    cols = ["series"] + [str(b) for b in exp["bins"]]
    rows = []
    for s in exp["series"]:
        row = {"series": s["label"]}
        row.update({str(b): c for b, c in zip(exp["bins"], s["counts"])})
        rows.append(row)
    return _md_table(cols, rows) + [""]


_RENDERERS = {"sweep": _render_sweep, "table": _render_table,
              "scalars": _render_scalars, "hist": _render_hist}


def render_markdown(doc: dict) -> str:
    env = doc.get("environment", {})
    cfg = doc.get("config", {})
    created = time.strftime("%Y-%m-%d %H:%M:%S UTC",
                            time.gmtime(doc.get("created_unix", 0)))
    lines = [
        "# Results",
        "",
        f"<!-- GENERATED by `python -m repro.bench run --suite "
        f"{doc['suite']}` — do not edit by hand; re-run the suite to "
        f"refresh. -->",
        "",
        f"Suite: **{doc['suite']}** · generated {created} · "
        f"jax {env.get('jax')} ({env.get('backend')}"
        f"{', ' + env['device_kind'] if 'device_kind' in env else ''}"
        f"{' x' + str(env['device_count']) if 'device_count' in env else ''}"
        f") · "
        f"python {env.get('python')}",
        "",
        f"Config: threads={cfg.get('threads')}, "
        f"steps={cfg.get('n_steps')}, replicas={cfg.get('n_replicas')}, "
        f"quick={cfg.get('quick')}",
        "",
        "Throughput is episodes per kilocycle of simulated bus time on the "
        "coherence machine (`core/sim/machine.py`, DESIGN.md §L1); curves "
        "are replica-ensemble means. These are *model* cycles, not "
        "wall-clock: shapes and orderings are the reproduction target, "
        "absolute values are not hardware claims.",
        "",
    ]
    for exp in doc["experiments"]:
        lines.append(f"## {exp['title']}")
        lines.append("")
        lines += _RENDERERS[exp["kind"]](exp)
    return "\n".join(lines).rstrip() + "\n"


def write_report(doc: dict, path: str) -> None:
    import os
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        f.write(render_markdown(doc))


#: Section header the `verify` CLI and the `verify` suite both target, so
#: the spliced matrix and the suite-rendered one occupy the same slot.
VERIFY_HEADER = "## Verified lock properties"


def splice_section(path: str, header: str, lines: list) -> None:
    """Replace (or append) one ``## ``-level section of a markdown file
    in place, preserving everything else. Creates the file if absent."""
    import os
    try:
        with open(path) as f:
            src = f.read().split("\n")
    except FileNotFoundError:
        src = ["# Results", ""]
    n = len(src)
    i = 0
    while i < n and src[i].strip() != header:
        i += 1
    before = src[:i]
    j = i + 1 if i < n else n
    while j < n and not src[j].startswith("## "):
        j += 1
    after = src[j:]
    while before and not before[-1].strip():
        before.pop()
    body = before + ["", header, ""] + [str(x) for x in lines]
    if any(s.strip() for s in after):
        while body and not body[-1].strip():
            body.pop()
        body += [""] + after
    text = "\n".join(body).rstrip() + "\n"
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def verify_section_lines(verdicts: list, note: str = "") -> list:
    """Markdown body for the verified-property matrix (the paper's
    comparison table with every cell machine-checked)."""
    from repro.core.locks import verify as verify_mod
    lines = []
    if note:
        lines += [note, ""]
    lines += _md_table(verify_mod.matrix_columns(),
                       verify_mod.matrix_rows(verdicts))
    lines.append("")
    lines.append(
        "Legend: `✓` proven by `core/locks/cfg.py` (structural) or "
        "`core/locks/verify.py` (exhaustive small-scope model check); "
        "`✗ declared` — the spec explicitly declares the weaker "
        "property via `s.expect(...)` and the analyzer confirmed the "
        "declaration matches reality; `saw n` — the largest "
        "waiting-window bypass observed across *all* interleavings at "
        "the certified scope.")
    return lines
