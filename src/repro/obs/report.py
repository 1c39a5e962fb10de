"""`python -m repro.obs report trace.jsonl` — render telemetry for humans.

Three record kinds land in one JSONL stream (`JsonlWriter`):

    {"kind": "trace",   "request_id": ..., "spans": [...]}
    {"kind": "rounds",  "rounds": R, "alive": [...], ...}
    {"kind": "metrics", "metrics": {...}}

The report renders each in order: trace records as an indented span tree
with durations, rounds records as a per-round table plus a sparkline of
the alive series, metrics records as a name → value table whose histogram
entries form the *health* section (count/mean/p50/p95/p99).  ``--json``
swaps the human rendering for one machine-readable document.  Exit code 2
when the file holds no renderable records — the CI smoke step relies on
that to catch an empty pipe.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from .rounds import RoundTrace

_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[int]) -> str:
    """Unicode mini-chart; total-safe for empty, single-point, all-zero
    and negative-valued series (negatives clamp to the bottom glyph)."""
    if not values:
        return ""
    hi = max(values)
    if hi <= 0:
        return _SPARK[0] * len(values)
    return "".join(_SPARK[min(max(int(v * 8 / hi), 0), 7)] for v in values)


def render_trace(d: Dict, out) -> None:
    rid = d.get("request_id") or "-"
    spans = d.get("spans", [])
    total = max((s["start_ms"] + s["dur_ms"] for s in spans), default=0.0)
    out.write(f"trace {rid}  ({total:.2f} ms, {len(spans)} spans)\n")
    for s in spans:
        indent = "  " * (int(s.get("depth", 0)) + 1)
        meta = s.get("meta") or {}
        tail = ("  " + " ".join(f"{k}={v}" for k, v in sorted(meta.items()))) if meta else ""
        out.write(f"{indent}{s['name']:<20} {s['dur_ms']:>9.3f} ms{tail}\n")


def render_rounds(d: Dict, out) -> None:
    rt = RoundTrace.from_dict(d)
    s = rt.summary()
    if not rt.rounds:
        # a 0-round trace is legal (empty graph / no-op update): summary()
        # carries no per-round keys, so bail before indexing any
        out.write("rounds 0  (empty trace)\n")
        return
    out.write(
        f"rounds {rt.rounds}"
        f"  alive {s.get('alive0', 0)}→{s.get('alive_final', 0)}"
        f"  selected {s.get('selected_total', 0)}"
    )
    if rt.tiles_total and s.get("tiles_skipped_mean") is not None:
        out.write(f"  tiles_skipped {s['tiles_skipped_mean']}/{rt.tiles_total}")
    out.write("\n")
    out.write(f"  alive    {_sparkline(rt.alive)}\n")
    out.write(f"  frontier {_sparkline(rt.frontier)}\n")
    out.write(f"  {'r':>4} {'alive':>8} {'frontier':>8} {'selected':>8} {'skipped':>8}\n")
    for r in range(rt.rounds):
        out.write(
            f"  {r:>4} {rt.alive[r]:>8} {rt.frontier[r]:>8}"
            f" {rt.selected[r]:>8} {rt.tiles_skipped[r]:>8}\n"
        )


def _fmt_histogram(val: Dict) -> str:
    """Health-section one-liner for a histogram snapshot: the SLO view."""
    if not val.get("count"):
        return "n=0"
    parts = [f"n={val['count']}"]
    for k in ("mean", "p50", "p95", "p99", "max"):
        if val.get(k) is not None:
            parts.append(f"{k}={val[k]}")
    return " ".join(parts)


def render_metrics(d: Dict, out) -> None:
    metrics = d.get("metrics", {})
    out.write(f"metrics ({len(metrics)} instruments)\n")
    for name, val in sorted(metrics.items()):
        if isinstance(val, dict):
            # histogram snapshot → the health line (quantiles, not the
            # raw bucket vector — promtext carries that)
            val = _fmt_histogram(val)
        out.write(f"  {name:<44} {val}\n")


_RENDERERS = {
    "trace": render_trace,
    "rounds": render_rounds,
    "metrics": render_metrics,
}


def _classify(d: Dict) -> str:
    kind = d.get("kind")
    return kind if kind in _RENDERERS else "unknown"


def _load(path: str, out) -> List[Dict]:
    records = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
            except json.JSONDecodeError as e:
                out.write(f"! line {lineno}: bad JSON ({e})\n")
                continue
            if not isinstance(d, dict):
                out.write(f"! line {lineno}: not an object\n")
                continue
            records.append(d)
    return records


def report(path: str, out=None) -> int:
    """Render every record in `path`; return the count rendered."""
    out = out or sys.stdout
    rendered = 0
    for d in _load(path, out):
        kind = _classify(d)
        fn = _RENDERERS.get(kind)
        if fn is None:
            out.write(f"! unknown kind {d.get('kind')!r}\n")
            continue
        fn(d, out)
        rendered += 1
    return rendered


def report_json(path: str, out=None) -> Dict:
    """Machine-readable digest: per-kind counts + the parsed records, with
    rounds records augmented by their `RoundTrace.summary()` scalars."""
    out = out or sys.stdout
    counts: Dict[str, int] = {}
    records = []
    for d in _load(path, out=_NullOut()):
        kind = _classify(d)
        if kind == "unknown":
            continue
        counts[kind] = counts.get(kind, 0) + 1
        if kind == "rounds":
            try:
                d = dict(d, summary=RoundTrace.from_dict(d).summary())
            except (KeyError, ValueError, TypeError):
                pass
        records.append(d)
    return dict(path=path, n_records=len(records), counts=counts,
                records=records)


class _NullOut:
    def write(self, _s: str) -> None:
        pass


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="render repro.obs JSONL telemetry (trace tree, "
                    "per-round series, metrics/health tables)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report", help="render a JSONL telemetry file")
    rp.add_argument("path", help="JSONL file written by the service / solver")
    rp.add_argument("--json", action="store_true",
                    help="emit a machine-readable JSON digest instead")
    args = p.parse_args(argv)

    if args.cmd == "report":
        if args.json:
            doc = report_json(args.path)
            print(json.dumps(doc, indent=2))
            return 0 if doc["n_records"] else 2
        n = report(args.path)
        if n == 0:
            print(f"# no renderable records in {args.path}", file=sys.stderr)
            return 2
        print(f"# rendered {n} records")
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
