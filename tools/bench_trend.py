"""Bench trajectory table + regression/misrepresentation gate over
BENCH_r*.json rounds.

The lesson this tool encodes: two recorded rounds ran on TFRT_CPU_0
and their numbers sat next to a real TPU measurement as if they
continued the same curve. Bench rounds
are only comparable WITHIN a backend, so this tool:

  1. classifies every round — `silicon`, `cpu_fallback`, or `no-data`
     (parsed null: crashed/timed-out runs) — from the parsed payload's
     explicit stamps (`backend`, `cpu_fallback`) with the device
     string as the cross-check,
  2. prints the trajectory table hard-separated by backend,
  3. flags `regression` when the headline value grows >10% between
     consecutive MEASURED rounds of the SAME backend (for rate-like
     units, a >10% drop), and
  4. flags `misrepresented` when a round's stamps contradict each
     other — a `cpu_fallback`/CPU-device round carrying a silicon
     backend stamp. Under `--check`, any regression or
     misrepresentation exits non-zero; the suite runs this so a future
     fallback round can never silently extend the silicon trajectory.

MULTICHIP_r*.json mesh dry runs fold into the same table: rounds that
stamp backend/device (tools/crypto_bench.py --mesh) get the identical
silicon/cpu_fallback hard separation and misrepresentation check;
legacy dryrun rounds (ok/rc/n_devices only) carry no backend evidence
and sit as no-data rows — visible, never extending either trajectory —
while a failed, non-skipped dryrun is a problem under --check.

Usage:
    python tools/bench_trend.py [--check] [--glob 'BENCH_r*.json']
                                [--multichip-glob 'MULTICHIP_r*.json']
                                [DIR]
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# the ONE classification vocabulary (shared with bench.py's stamp and
# the silicon watchdog)
from tendermint_tpu.crypto.tpu.backend import classify_stamps  # noqa: E402

REGRESSION_PCT = 10.0


def _rate_unit(unit: str) -> bool:
    u = (unit or "").lower()
    return "/s" in u or "per_sec" in u or "per sec" in u


def classify(entry: dict) -> dict:
    """One BENCH_r*.json -> {round, backend, value, unit, device,
    problems}. backend ∈ silicon | cpu_fallback | no-data."""
    parsed = entry.get("parsed")
    row = {"round": entry.get("n"), "rc": entry.get("rc"),
           "backend": "no-data", "value": None, "unit": None,
           "device": None, "metric": None, "problems": []}
    if not isinstance(parsed, dict):
        return row
    device = str(parsed.get("device", ""))
    row["device"] = device or None
    row["value"] = parsed.get("value")
    row["unit"] = parsed.get("unit")
    row["metric"] = parsed.get("metric")
    backend, problems = classify_stamps(
        parsed.get("backend", ""), bool(parsed.get("cpu_fallback")),
        device)
    row["backend"] = backend
    row["problems"].extend(problems)
    return row


def classify_multichip(entry: dict) -> dict:
    """One MULTICHIP_r*.json -> a trajectory row. Newer rounds
    (crypto_bench --mesh) stamp backend/device inline and get the same
    hard separation; legacy dryruns (ok/rc/n_devices/tail only) have
    no backend evidence and no measured value, so they sit as no-data
    rows. A failed, non-skipped dryrun is a problem."""
    parsed = entry.get("parsed")
    src = parsed if isinstance(parsed, dict) else entry
    row = {"round": entry.get("n"), "rc": entry.get("rc"),
           "backend": "no-data", "value": src.get("value"),
           "unit": src.get("unit"),
           "metric": src.get("metric") or "multichip_dryrun",
           "device": src.get("device"),
           "n_devices": src.get("n_devices", entry.get("n_devices")),
           "problems": []}
    # crypto_bench --evict stamps a `degraded` block and the launch
    # ledger stamps each record's active device set: a round that ran
    # on fewer devices than the fabric holds measured different
    # hardware, so it must not feed the full-mesh regression chain.
    deg = src.get("degraded")
    active = (deg.get("active_devices") if isinstance(deg, dict)
              else src.get("active_devices"))
    if isinstance(active, list):
        row["active_devices"] = len(active)
    row["degraded"] = bool(isinstance(deg, dict) or (
        isinstance(active, list) and row["n_devices"]
        and len(active) < int(row["n_devices"])))
    if entry.get("skipped"):
        return row
    if src.get("backend") or src.get("device"):
        backend, problems = classify_stamps(
            src.get("backend", ""), bool(src.get("cpu_fallback")),
            str(src.get("device", "")))
        row["backend"] = backend
        row["problems"].extend(problems)
    ok = entry.get("ok", entry.get("rc") == 0)
    if not ok:
        row["problems"].append(
            f"multichip dryrun failed (rc={entry.get('rc')})")
    return row


def load_rounds(paths: list[str], kind: str = "bench") -> list[dict]:
    classifier = classify_multichip if kind == "multichip" else classify
    rows = []
    for p in sorted(paths):
        try:
            with open(p) as f:
                entry = json.load(f)
        except (OSError, ValueError) as e:
            rows.append({"round": os.path.basename(p), "rc": None,
                         "backend": "no-data", "value": None,
                         "unit": None, "device": None, "metric": None,
                         "problems": [f"unreadable: {e!r}"]})
            continue
        row = classifier(entry)
        row["file"] = os.path.basename(p)
        rows.append(row)
    return rows


def find_regressions(rows: list[dict]) -> list[str]:
    """>10% headline-value growth (or rate drop) between consecutive
    MEASURED rounds of the same backend. no-data rounds don't break
    the chain — r01 vs a hypothetical silicon r06 still compares."""
    out = []
    last_by_backend: dict[str, dict] = {}
    for row in rows:
        b = row["backend"]
        if b == "no-data" or row["value"] is None \
                or row.get("degraded"):
            continue
        prev = last_by_backend.get(b)
        if prev is not None and prev["value"]:
            if _rate_unit(row["unit"]):
                delta = (prev["value"] - row["value"]) / prev["value"]
                verb = "dropped"
            else:
                delta = (row["value"] - prev["value"]) / prev["value"]
                verb = "grew"
            if delta * 100.0 > REGRESSION_PCT:
                out.append(
                    f"regression[{b}]: {prev.get('file')} -> "
                    f"{row.get('file')}: {row['metric']} {verb} "
                    f"{delta * 100.0:.1f}% ({prev['value']} -> "
                    f"{row['value']} {row['unit']})")
        last_by_backend[b] = row
    return out


def render_table(rows: list[dict]) -> str:
    lines = []
    for backend in ("silicon", "cpu_fallback", "no-data"):
        sel = [r for r in rows if r["backend"] == backend]
        if not sel:
            continue
        lines.append(f"-- {backend} --")
        for r in sel:
            val = (f"{r['value']} {r['unit']}" if r["value"] is not None
                   else f"(rc={r['rc']})")
            nd = (f" n_devices={r['n_devices']}"
                  if r.get("n_devices") else "")
            if r.get("degraded"):
                ad = r.get("active_devices")
                nd += (f" degraded({ad}/{r['n_devices']})"
                       if ad and r.get("n_devices") else " degraded")
            flag = "  !! " + "; ".join(r["problems"]) if r["problems"] \
                else ""
            lines.append(f"  {r.get('file', r['round']):<18} {val:<18} "
                         f"device={r['device']}{nd}{flag}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="BENCH_r*.json trajectory table + regression gate")
    ap.add_argument("dir", nargs="?", default=".",
                    help="directory holding the BENCH files")
    ap.add_argument("--glob", default="BENCH_r*.json")
    ap.add_argument("--multichip-glob", default="MULTICHIP_r*.json")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on any regression or "
                         "misrepresented round")
    args = ap.parse_args(argv)

    paths = _glob.glob(os.path.join(args.dir, args.glob))
    if not paths:
        print(f"no files match {args.glob} in {args.dir}",
              file=sys.stderr)
        return 2
    rows = load_rounds(paths)
    mc_paths = _glob.glob(os.path.join(args.dir, args.multichip_glob))
    rows += load_rounds(mc_paths, kind="multichip")
    print(render_table(rows))

    problems = [p for r in rows for p in r["problems"]]
    regressions = find_regressions(rows)
    for msg in problems + regressions:
        print(f"TREND: {msg}")
    if args.check and (problems or regressions):
        print("FAILED")
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
