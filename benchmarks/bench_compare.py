"""Bench regression sentinel: diff the newest BENCH round against a
trailing baseline of prior rounds.

A series of ``BENCH_rNN.json`` artifacts (one bench.py payload each,
under a ``parsed`` key) is a performance history; this module is its
reader.  It compares the newest HEALTHY round's payload against the
per-key median of the trailing window of prior healthy rounds, with
per-key tolerance bands, and emits a one-line verdict plus a JSON report.
The direction-aware bands are the mechanism for a per-cell regression
bound; no series is checked in at present (tests/test_costledger.py
drives it on inline synthetic rounds).

Contract awareness (why this is not a generic json differ):

  * a round whose payload has no headline ``value`` or carries an
    ``error`` did not measure: it is excluded from baselines and never
    judged.
  * descriptor keys (metric name, unit, device, corpus size, chip peak)
    are configuration, not performance — excluded.
  * direction matters: ``*_ms`` / latency / overhead keys regress
    UPWARD; throughput keys regress DOWNWARD.  Latency bands are looser
    (default 50% vs 25%): single-shot p50s are noisier than rates.

CLI: ``python -m benchmarks.bench_compare [--dir .] [--json]`` — exit 1
on a regression verdict, 0 otherwise.
"""

from __future__ import annotations

import argparse
import glob as glob_mod
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

# Configuration/descriptor keys — not performance.
DESCRIPTOR_KEYS = frozenset(
    {
        "metric",
        "unit",
        "device",
        "platform",
        "device_kind",
        "device_count",
        "error",
        "n_docs",
        "tokens_per_doc",
        "device_peak_tflops_bf16",
    }
)

# Tolerance bands: a higher-is-better key regresses when it drops below
# (1 - HIGHER_TOL) x baseline; a lower-is-better key regresses when it
# rises above (1 + LOWER_TOL) x baseline.
HIGHER_TOL = 0.25
LOWER_TOL = 0.50

# Trailing-baseline window: the newest healthy round is judged against
# the per-key median of up to this many prior healthy rounds.
WINDOW = 4

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def is_healthy(parsed: Dict[str, Any]) -> bool:
    """A round that actually measured: no error, a real headline value."""
    return parsed.get("error") is None and parsed.get("value") is not None


def lower_is_better(key: str) -> bool:
    k = key.lower()
    return (
        k.endswith("_ms")
        or "_ms_" in k
        or "latency" in k
        or "overhead" in k
    )


def _numeric_items(parsed: Dict[str, Any]) -> Dict[str, float]:
    """Comparable scalars only — lists (per-run series) and strings are
    shape, not a single measurement."""
    out: Dict[str, float] = {}
    for key, value in parsed.items():
        if key in DESCRIPTOR_KEYS or isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            out[key] = float(value)
    return out


def load_rounds(
    bench_dir: str, pattern: str = "BENCH_r*.json"
) -> List[Tuple[str, Dict[str, Any]]]:
    """[(round_name, parsed_payload)] ordered by round number."""
    rounds: List[Tuple[int, str, Dict[str, Any]]] = []
    for path in glob_mod.glob(os.path.join(bench_dir, pattern)):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path) as fh:
                artifact = json.load(fh)
        except (OSError, ValueError):
            continue
        parsed = artifact.get("parsed")
        if isinstance(parsed, dict):
            rounds.append((int(m.group(1)), os.path.basename(path), parsed))
    rounds.sort()
    return [(name, parsed) for _n, name, parsed in rounds]


def _median(values: List[float]) -> float:
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    if n % 2:
        return ordered[mid]
    return 0.5 * (ordered[mid - 1] + ordered[mid])


def compare(
    latest: Dict[str, Any],
    baseline_rounds: List[Dict[str, Any]],
    *,
    higher_tol: float = HIGHER_TOL,
    lower_tol: float = LOWER_TOL,
) -> Dict[str, Any]:
    """Judge one payload against prior healthy payloads.

    Per key: baseline = median over the rounds that carry it; direction
    and tolerance from the key name; ``slack`` is the signed distance to
    the band edge (negative = regression).  Keys with no baseline (new
    in this round) or a zero baseline are reported but never judged."""
    current = _numeric_items(latest)
    checks: List[Dict[str, Any]] = []
    for key in sorted(current):
        history = [
            vals[key]
            for vals in (_numeric_items(r) for r in baseline_rounds)
            if key in vals
        ]
        if not history:
            checks.append(
                {"key": key, "latest": current[key], "baseline": None,
                 "ratio": None, "ok": True, "note": "new-key"}
            )
            continue
        baseline = _median(history)
        if baseline == 0:
            checks.append(
                {"key": key, "latest": current[key], "baseline": baseline,
                 "ratio": None, "ok": True, "note": "zero-baseline"}
            )
            continue
        ratio = current[key] / baseline
        if lower_is_better(key):
            direction, tolerance = "lower-better", lower_tol
            slack = (1.0 + tolerance) - ratio
        else:
            direction, tolerance = "higher-better", higher_tol
            slack = ratio - (1.0 - tolerance)
        checks.append(
            {
                "key": key,
                "latest": current[key],
                "baseline": round(baseline, 6),
                "ratio": round(ratio, 4),
                "direction": direction,
                "tolerance": tolerance,
                "slack": round(slack, 4),
                "ok": slack >= 0,
            }
        )
    judged = [c for c in checks if c.get("slack") is not None]
    failed = [c for c in judged if not c["ok"]]
    worst: Optional[Dict[str, Any]] = None
    if judged:
        worst = min(judged, key=lambda c: c["slack"])
    if not judged:
        verdict = "insufficient-data"
    elif failed:
        verdict = "regression"
    else:
        verdict = "ok"
    return {
        "verdict": verdict,
        "checks": checks,
        "judged": len(judged),
        "failed": [c["key"] for c in failed],
        "worst": worst,
    }


def compare_series(
    rounds: List[Tuple[str, Dict[str, Any]]],
    *,
    window: int = WINDOW,
    higher_tol: float = HIGHER_TOL,
    lower_tol: float = LOWER_TOL,
) -> Dict[str, Any]:
    """Judge the newest healthy round of the series against the trailing
    window of prior healthy rounds."""
    healthy = [(name, p) for name, p in rounds if is_healthy(p)]
    skipped = [name for name, p in rounds if not is_healthy(p)]
    if not healthy:
        return {
            "verdict": "skipped",
            "reason": "no healthy rounds",
            "skipped_rounds": skipped,
            "worst": None,
        }
    latest_name, latest = healthy[-1]
    baseline = healthy[max(0, len(healthy) - 1 - window):-1]
    if not baseline:
        return {
            "verdict": "insufficient-data",
            "reason": f"{latest_name} is the only healthy round",
            "latest": latest_name,
            "skipped_rounds": skipped,
            "worst": None,
        }
    result = compare(
        latest,
        [p for _n, p in baseline],
        higher_tol=higher_tol,
        lower_tol=lower_tol,
    )
    result["latest"] = latest_name
    result["baseline_rounds"] = [n for n, _p in baseline]
    result["skipped_rounds"] = skipped
    return result


def verdict_line(result: Dict[str, Any]) -> str:
    """The one-line human summary."""
    verdict = result.get("verdict")
    if verdict in ("skipped", "insufficient-data"):
        return f"bench-compare: {verdict} ({result.get('reason', '')})"
    base = ",".join(result.get("baseline_rounds", []))
    worst = result.get("worst")
    worst_txt = ""
    if worst is not None:
        worst_txt = (
            f" worst={worst['key']} ratio={worst['ratio']}"
            f" ({worst['direction']}, tol {worst['tolerance']:g})"
        )
    if verdict == "regression":
        return (
            f"bench-compare: REGRESSION {result['latest']} vs [{base}] — "
            f"{len(result['failed'])}/{result['judged']} keys out of band:"
            f" {','.join(result['failed'])};{worst_txt}"
        )
    return (
        f"bench-compare: ok {result['latest']} vs [{base}] — "
        f"{result['judged']} keys in band;{worst_txt}"
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="bench_compare",
        description="diff the newest BENCH_r*.json against the trailing "
        "baseline of prior rounds",
    )
    parser.add_argument(
        "--dir", default=".", help="directory holding BENCH_r*.json"
    )
    parser.add_argument(
        "--window", type=int, default=WINDOW,
        help=f"trailing baseline rounds (default {WINDOW})",
    )
    parser.add_argument(
        "--json", action="store_true", help="full JSON report"
    )
    args = parser.parse_args(argv)
    rounds = load_rounds(args.dir)
    result = compare_series(rounds, window=args.window)
    if args.json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        print(verdict_line(result))
    return 1 if result.get("verdict") == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
