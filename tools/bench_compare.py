"""Flag regressions of the host control-plane harness against the best
same-backend baseline.

`tools/control_plane_bench.py` appends every gated headline (CPU numbers
under CPU names: `control_plane_*`, `resize_*`) to
`tools/bench_history.jsonl`; this tool compares the LATEST entry of each
(metric, backend) group against the BEST prior same-backend value and
exits nonzero when the drop exceeds the threshold (default 2%). Speed on
the chip is not judged here: that is `benchmark/` and
`PERF_LEDGER.jsonl`.

Rules:
- groups are (metric, backend);
- value <= 0 entries are markers (a failed or withheld run), not
  measurements — skipped both as baseline and as the judged entry;
- direction comes from the unit: seconds/ms/bytes are lower-is-better,
  everything else higher-is-better.

Run: python tools/bench_compare.py [--threshold-pct 2]
     [--history tools/bench_history.jsonl] [--metric NAME]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_HISTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_history.jsonl")

LOWER_IS_BETTER_UNITS = ("s", "ms", "sec", "seconds", "bytes", "b")


def load_history(path: str) -> list[dict]:
    entries = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict):
                    entries.append(obj)
    except OSError:
        pass
    return entries


def _measurable(entry: dict) -> bool:
    try:
        return float(entry.get("value", 0.0)) > 0.0
    except (TypeError, ValueError):
        return False


def lower_is_better(unit: str) -> bool:
    return str(unit).strip().lower() in LOWER_IS_BETTER_UNITS


def compare(entries: list[dict], threshold_pct: float,
            metric: str = "") -> list[dict]:
    """Returns one verdict dict per (metric, backend) group that has a
    judgeable latest entry; verdicts with `regression: True` dropped
    more than `threshold_pct` vs the best prior same-backend value."""
    groups: dict[tuple, list[dict]] = {}
    for e in entries:
        m = str(e.get("metric", "") or "")
        if not m or (metric and m != metric):
            continue
        groups.setdefault((m, str(e.get("backend", "") or "")),
                          []).append(e)
    verdicts = []
    for (m, backend), group in sorted(groups.items()):
        latest = next((e for e in reversed(group) if _measurable(e)), None)
        if latest is None:
            continue
        prior = [e for e in group if e is not latest and _measurable(e)]
        if not prior:
            verdicts.append({"metric": m, "backend": backend,
                             "value": float(latest["value"]),
                             "baseline": None, "regression": False,
                             "note": "no prior baseline"})
            continue
        lower = lower_is_better(str(latest.get("unit", "")))
        values = [float(e["value"]) for e in prior]
        baseline = min(values) if lower else max(values)
        value = float(latest["value"])
        if lower:
            drop_pct = 100.0 * (value - baseline) / baseline
        else:
            drop_pct = 100.0 * (baseline - value) / baseline
        verdicts.append({
            "metric": m, "backend": backend, "value": value,
            "unit": str(latest.get("unit", "")),
            "baseline": baseline,
            "baseline_commit": next(
                (str(e.get("commit", "")) for e in prior
                 if float(e["value"]) == baseline), ""),
            "drop_pct": round(drop_pct, 3),
            "regression": drop_pct > threshold_pct,
        })
    return verdicts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="bench_compare")
    parser.add_argument("--history", default=DEFAULT_HISTORY)
    parser.add_argument("--threshold-pct", type=float, default=2.0,
                        help="fail when the latest measurable entry "
                             "drops more than this vs the best prior "
                             "same-backend value")
    parser.add_argument("--metric", default="",
                        help="judge only this metric")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    entries = load_history(args.history)
    if not entries:
        print(f"no bench history at {args.history} — nothing to judge",
              file=sys.stderr)
        return 0
    verdicts = compare(entries, args.threshold_pct, metric=args.metric)
    if args.json:
        print(json.dumps(verdicts, indent=1, sort_keys=True))
    else:
        for v in verdicts:
            if v.get("baseline") is None:
                print(f"{v['metric']} [{v['backend']}]: "
                      f"{v['value']} ({v['note']})")
                continue
            tag = "REGRESSION" if v["regression"] else "ok"
            print(f"{v['metric']} [{v['backend']}]: {v['value']} "
                  f"{v.get('unit', '')} vs best {v['baseline']} "
                  f"({v.get('baseline_commit') or 'unknown commit'}) — "
                  f"drop {v['drop_pct']}% [{tag}]")
    return 1 if any(v["regression"] for v in verdicts) else 0


if __name__ == "__main__":
    raise SystemExit(main())
