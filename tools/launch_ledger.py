"""Launch-ledger analyzer: per-workload cost attribution from any
ledger export surface (docs/OBSERVABILITY.md "Launch ledger & silicon
watchdog").

Answers the post-round question the raw ring can't: which verify
plane bought what with its device time and bytes — and did any of it
actually run on silicon. Input is auto-detected:

  * a `/debug/launches` JSON dump ({records, rollup, watchdog, hbm});
  * a bench.py output line / BENCH_r*.json round carrying a
    `ledger_rollup` block (parsed payloads are searched too);
  * an e2e run report embedding `launch_ledger` ({node: rollup});
  * `--url http://host:port/debug/launches` to pull a live node.

Prints the per-workload cost-attribution table (launches, lanes,
bytes each way, backend + verdict mix, exec p50/p99), a per-kernel
table when raw records are present, the HBM residency map, and ONE
machine-readable `LEDGER_SUMMARY <json>` line for drivers/CI — same
contract as bench.py's BENCH lines: greppable, single line, stable
keys.

Usage:
    python tools/launch_ledger.py FILE [FILE ...]
    python tools/launch_ledger.py --url http://127.0.0.1:6060/debug/launches
    python tools/launch_ledger.py --url 127.0.0.1:6060 --workload probe
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request

_WORKLOAD_COLS = ("launches", "lanes", "bytes_h2d", "bytes_d2h",
                  "exec_ms_p50", "exec_ms_p99")


def _fmt_bytes(n: int | float | None) -> str:
    if not n:
        return "0"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"  # pragma: no cover - loop always returns


def _fmt_mix(d: dict | None) -> str:
    if not d:
        return "-"
    return ",".join(f"{k}:{v}" for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1]))


def fetch(url: str, timeout: float = 10.0) -> dict:
    """GET a /debug/launches payload. Accepts bare host:port."""
    if "://" not in url:
        url = f"http://{url}"
    if "/debug/" not in url:
        url = url.rstrip("/") + "/debug/launches"
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _rollup_of(obj: dict) -> dict | None:
    """A per-workload rollup dict hiding anywhere inside one JSON
    object: a /debug/launches payload (rollup.workloads), a bare
    ledger.rollup() result (workloads), a BENCH line or its driver
    wrapper (ledger_rollup / parsed.ledger_rollup), or already the
    {workload: {launches, ...}} mapping itself."""
    if not isinstance(obj, dict):
        return None
    for key in ("rollup", "parsed"):
        inner = obj.get(key)
        if isinstance(inner, dict):
            found = _rollup_of(inner)
            if found is not None:
                return found
    for key in ("ledger_rollup", "workloads"):
        inner = obj.get(key)
        if isinstance(inner, dict) and all(
                isinstance(v, dict) and "launches" in v
                for v in inner.values()):
            return inner
    if obj and all(isinstance(v, dict) and "launches" in v
                   for v in obj.values()):
        return obj
    return None


def extract(payload: dict) -> list[tuple[str, dict, list[dict]]]:
    """[(label, per-workload rollup, raw records)] from one parsed
    input. An e2e report's launch_ledger block yields one entry per
    node; everything else yields at most one entry labeled ''."""
    out: list[tuple[str, dict, list[dict]]] = []
    ll = payload.get("launch_ledger") if isinstance(payload, dict) \
        else None
    if isinstance(ll, dict) and ll:
        for node in sorted(ll):
            roll = _rollup_of(ll[node]) or {}
            recs = ll[node].get("records") \
                if isinstance(ll[node], dict) else None
            # rollup() carries an int `records` count — only a list is
            # the raw ring
            out.append((str(node), roll,
                        recs if isinstance(recs, list) else []))
        return out
    roll = _rollup_of(payload)
    recs = payload.get("records") if isinstance(payload, dict) else None
    if roll is not None or recs:
        out.append(("", roll or {}, recs if isinstance(recs, list)
                    else []))
    return out


def kernel_rollup(records: list[dict]) -> dict:
    """{kernel: {launches, lanes, bytes_h2d, compile_misses}} — the
    per-dispatch-site cut of the same records."""
    out: dict[str, dict] = {}
    for r in records:
        k = out.setdefault(str(r.get("kernel")), {
            "launches": 0, "lanes": 0, "bytes_h2d": 0,
            "compile_misses": 0})
        k["launches"] += 1
        k["lanes"] += r.get("lanes") or 0
        k["bytes_h2d"] += r.get("bytes_h2d") or 0
        if r.get("compile_cache") == "miss":
            k["compile_misses"] += 1
    return out


def render_workloads(workloads: dict) -> str:
    header = (f"  {'workload':<12} {'launches':>8} {'lanes':>9} "
              f"{'h2d':>10} {'d2h':>10} {'exec p50':>9} "
              f"{'exec p99':>9}  backends / verdicts / table-read lanes")
    lines = [header]
    for name, w in sorted(workloads.items(),
                          key=lambda kv: -kv[1].get("launches", 0)):
        lines.append(
            f"  {name:<12} {w.get('launches', 0):>8} "
            f"{w.get('lanes', 0):>9} "
            f"{_fmt_bytes(w.get('bytes_h2d')):>10} "
            f"{_fmt_bytes(w.get('bytes_d2h')):>10} "
            f"{w.get('exec_ms_p50', 0):>9} {w.get('exec_ms_p99', 0):>9}"
            f"  {_fmt_mix(w.get('backends'))} / "
            f"{_fmt_mix(w.get('verdicts'))} / "
            f"{_fmt_mix(w.get('rows_lanes'))}")
    return "\n".join(lines)


def render_kernels(records: list[dict]) -> str:
    lines = [f"  {'kernel':<18} {'launches':>8} {'lanes':>9} "
             f"{'h2d':>10} {'compiles':>8}"]
    for name, k in sorted(kernel_rollup(records).items(),
                          key=lambda kv: -kv[1]["launches"]):
        lines.append(f"  {name:<18} {k['launches']:>8} {k['lanes']:>9} "
                     f"{_fmt_bytes(k['bytes_h2d']):>10} "
                     f"{k['compile_misses']:>8}")
    return "\n".join(lines)


def summarize(sections: list[tuple[str, dict, list[dict]]],
              watchdog: dict | None, hbm: dict | None) -> dict:
    """The LEDGER_SUMMARY payload: totals a driver can diff between
    rounds without reparsing tables."""
    backends: dict[str, int] = {}
    verdicts: dict[str, int] = {}
    rows_lanes: dict[str, int] = {}
    total = {"launches": 0, "lanes": 0, "bytes_h2d": 0, "bytes_d2h": 0}
    by_workload: dict[str, int] = {}
    for _label, workloads, _recs in sections:
        for wname, w in workloads.items():
            by_workload[wname] = by_workload.get(wname, 0) + \
                w.get("launches", 0)
            for key in total:
                total[key] += w.get(key, 0)
            for b, n in (w.get("backends") or {}).items():
                backends[b] = backends.get(b, 0) + n
            for v, n in (w.get("verdicts") or {}).items():
                verdicts[v] = verdicts.get(v, 0) + n
            for v, n in (w.get("rows_lanes") or {}).items():
                rows_lanes[v] = rows_lanes.get(v, 0) + n
    out = dict(total, workloads=by_workload, backends=backends,
               verdicts=verdicts, rows_lanes=rows_lanes)
    if watchdog:
        out["effective_backend"] = watchdog.get("effective_backend")
    if hbm:
        out["hbm_bytes"] = {dev: sum(kinds.values())
                            for dev, kinds in hbm.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="launch-ledger cost-attribution tables")
    ap.add_argument("files", nargs="*",
                    help="JSON exports: /debug/launches dumps, BENCH "
                         "rounds with ledger_rollup, e2e run reports")
    ap.add_argument("--url", action="append", default=[],
                    help="fetch a live /debug/launches (host:port ok); "
                         "repeatable")
    ap.add_argument("--workload", default=None,
                    help="only this workload tag in the tables")
    args = ap.parse_args(argv)
    if not args.files and not args.url:
        ap.error("need at least one FILE or --url")

    sections: list[tuple[str, dict, list[dict]]] = []
    watchdog: dict | None = None
    hbm: dict | None = None
    failures = 0
    for src in args.files + args.url:
        try:
            if src in args.url:
                payload = fetch(src)
            else:
                with open(src) as f:
                    payload = json.load(f)
        except (OSError, ValueError) as e:
            print(f"ERROR: {src}: {e!r}", file=sys.stderr)
            failures += 1
            continue
        got = extract(payload)
        if not got:
            print(f"ERROR: {src}: no ledger rollup/records found",
                  file=sys.stderr)
            failures += 1
            continue
        for label, roll, recs in got:
            sections.append((label or src, roll, recs))
        if isinstance(payload.get("watchdog"), dict):
            watchdog = payload["watchdog"]
        if isinstance(payload.get("hbm"), dict):
            hbm = payload["hbm"]

    if args.workload:
        sections = [
            (label,
             {k: v for k, v in roll.items() if k == args.workload},
             [r for r in recs if r.get("workload") == args.workload])
            for label, roll, recs in sections]

    for label, roll, recs in sections:
        print(f"== {label} ==")
        if roll:
            print(render_workloads(roll))
        if recs:
            print(render_kernels(recs))
        if not roll and not recs:
            print("  (empty ledger)")
    if watchdog:
        print("watchdog: effective_backend="
              f"{watchdog.get('effective_backend')} launches_in_window="
              f"{watchdog.get('launches_in_window')}")
    if hbm:
        for dev, kinds in sorted(hbm.items()):
            per = ", ".join(f"{k}={_fmt_bytes(n)}"
                            for k, n in sorted(kinds.items()))
            print(f"hbm: {dev}: {per} "
                  f"(total {_fmt_bytes(sum(kinds.values()))})")

    print("LEDGER_SUMMARY " + json.dumps(
        summarize(sections, watchdog, hbm), sort_keys=True))
    return 1 if failures or not sections else 0


if __name__ == "__main__":
    sys.exit(main())
