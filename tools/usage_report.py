"""Offline usage report: render tenant/goodput tables from a history
snapshot directory, no server required.

``python -m tools.usage_report <snapshot-dir>`` loads the
``history.json`` the serving process left behind (see
``localai_tpu.obs.history``: atomic writer, ``LOCALAI_HISTORY_DIR``) and
prints per-tenant delivered tokens / requests, per-model goodput, and
the waste decomposition — each as the latest cumulative counter value
plus the delta across the loaded window, so "who burned the device this
afternoon" is answerable from a dead snapshot.

``--ingest-autoscale <dir-or-file>...`` folds the ``autoscale_report.json``
artifact telemetry_smoke round 20 leaves behind into the same store: the
fleet's capacity trajectory replays at its recorded timestamps and the decision counts / cold-start latency land as
``autoscale.*`` series. ``--save`` writes the merged snapshot back
(tmp + ``os.replace``, same as the live writer).

Raw API keys never appear here for the same reason they never appear in
/metrics: the ledger only ever stored hashed ``t-…`` buckets, so the
snapshot is clean by construction.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Any, Optional

from localai_tpu.obs.history import CAPACITY, History


def _series_span(h: History, name: str, res: int) -> Optional[dict]:
    """Latest value + delta over the ring for one counter series."""
    q = h.query(name, res=res)
    if not q or not q["points"]:
        return None
    pts = q["points"]
    first, last = pts[0], pts[-1]
    return {
        "latest": last["value"],
        "delta": last["value"] - first["value"],
        "from_ts": first["ts"],
        "to_ts": last["ts"],
        "points": len(pts),
    }


def _collect(h: History, prefix: str, res: int) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for name in h.series_names():
        if not name.startswith(prefix + "."):
            continue
        span = _series_span(h, name, res)
        if span is not None:
            out[name[len(prefix) + 1:]] = span
    return out


def _table(title: str, header: list[str], rows: list[list[Any]],
           out) -> None:
    out.write(f"\n{title}\n")
    if not rows:
        out.write("  (no data)\n")
        return
    widths = [max(len(str(header[i])),
                  *(len(str(r[i])) for r in rows))
              for i in range(len(header))]
    fmt = "  " + "  ".join(f"{{:<{w}}}" for w in widths) + "\n"
    out.write(fmt.format(*header))
    out.write(fmt.format(*("-" * w for w in widths)))
    for r in rows:
        out.write(fmt.format(*(str(c) for c in r)))


def build_report(h: History, *, res: int = 10) -> dict:
    """The machine-readable report; the text renderer walks this."""
    tenants = _collect(h, "tenant_tokens", res)
    tenant_reqs = _collect(h, "tenant_requests", res)
    report = {
        "resolution_s": res,
        "tenants": {
            t: {"delivered_tokens": span,
                "requests": tenant_reqs.get(t)}
            for t, span in tenants.items()
        },
        "goodput_tokens": _collect(h, "goodput_tokens", res),
        "waste_tokens": _collect(h, "waste_tokens", res),
        "engine": {
            "tokens_generated": _collect(h, "tokens_generated", res),
            "requests_shed": _collect(h, "requests_shed", res),
        },
        "autoscale": _collect(h, "autoscale", res),
        "fleet_target_replicas": _collect(h, "fleet_target_replicas",
                                          res),
        "series_total": len(h.series_names()),
    }
    # tenants only present in the requests series (all-waste tenants
    # never delivered a token but still made requests)
    for t, span in tenant_reqs.items():
        report["tenants"].setdefault(
            t, {"delivered_tokens": None, "requests": span})
    return report


def render_text(report: dict, out=None) -> None:
    out = out or sys.stdout
    res = report["resolution_s"]
    out.write(f"usage report @ {res}s resolution "
              f"({report['series_total']} series in store)\n")

    rows = []
    for tenant in sorted(report["tenants"]):
        cell = report["tenants"][tenant]
        tok, req = cell["delivered_tokens"], cell["requests"]
        rows.append([
            tenant,
            int(tok["latest"]) if tok else 0,
            int(tok["delta"]) if tok else 0,
            int(req["latest"]) if req else 0,
            int(req["delta"]) if req else 0,
        ])
    _table("per-tenant (hashed buckets — raw keys never stored)",
           ["tenant", "tokens", "Δtokens", "requests", "Δrequests"],
           rows, out)

    rows = [[m, int(s["latest"]), int(s["delta"])]
            for m, s in sorted(report["goodput_tokens"].items())]
    _table("goodput by model", ["model", "tokens", "Δtokens"], rows, out)

    rows = [[r, int(s["latest"]), int(s["delta"])]
            for r, s in sorted(report["waste_tokens"].items())]
    _table("waste by reason", ["reason", "tokens", "Δtokens"], rows, out)

    if report["autoscale"] or report["fleet_target_replicas"]:
        rows = [[f"target_replicas.{m}", s["latest"], s["points"],
                 time.strftime("%Y-%m-%d %H:%M",
                               time.localtime(s["to_ts"]))]
                for m, s in sorted(
                    report["fleet_target_replicas"].items())]
        rows += [[m, s["latest"], s["points"],
                  time.strftime("%Y-%m-%d %H:%M",
                                time.localtime(s["to_ts"]))]
                 for m, s in sorted(report["autoscale"].items())]
        _table("elastic capacity",
               ["metric", "last", "points", "as of"], rows, out)


def _autoscale_files(paths: list[str]) -> list[str]:
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(
                os.path.join(p, "autoscale_report*.json"))))
        else:
            files.append(p)
    return files


def ingest_autoscale(h: History, paths: list[str]) -> int:
    """Fold ``autoscale_report.json`` artifacts (telemetry_smoke round
    20) into the store: the capacity trajectory replays point-by-point
    at its recorded timestamps (``fleet_target_replicas.<model>``), and
    the run's decision counts / peak / cold-start latency land as
    ``autoscale.*`` gauges at the file's mtime. Returns points ingested;
    bad files are skipped with a stderr note."""
    ingested = 0
    for path in _autoscale_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            ts = os.path.getmtime(path)
        except (OSError, ValueError) as e:
            sys.stderr.write(f"usage_report: skipping {path}: {e}\n")
            continue
        if not isinstance(doc, dict):
            sys.stderr.write(f"usage_report: skipping {path}: not a "
                             f"JSON object\n")
            continue
        series = doc.get("target_series") or {}
        name = series.get("series") or "fleet_target_replicas.unknown"
        for pt in series.get("points") or []:
            if isinstance(pt, dict) and isinstance(
                    pt.get("value"), (int, float)):
                h.record(name, float(pt["value"]),
                         ts=float(pt.get("ts") or ts))
                ingested += 1
        for action, count in (doc.get("decisions") or {}).items():
            if isinstance(count, (int, float)):
                h.record(f"autoscale.decisions_{action}", float(count),
                         ts=ts)
                ingested += 1
        for key in ("peak_healthy", "cold_start_ms"):
            val = doc.get(key)
            if isinstance(val, (int, float)):
                h.record(f"autoscale.{key}", float(val), ts=ts)
                ingested += 1
    return ingested


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("snapshot_dir", nargs="?", default="",
                        help="directory holding history.json (the live "
                             "LOCALAI_HISTORY_DIR)")
    parser.add_argument("--res", type=int, default=10,
                        choices=sorted(CAPACITY),
                        help="ring resolution to report at (seconds)")
    parser.add_argument("--ingest-autoscale", nargs="+", default=[],
                        metavar="PATH",
                        help="autoscale_report*.json files or "
                             "directories (telemetry_smoke round 20) to "
                             "fold into the store as capacity series")
    parser.add_argument("--save", action="store_true",
                        help="write the (merged) snapshot back to "
                             "snapshot_dir")
    parser.add_argument("--json", action="store_true",
                        help="emit the machine-readable report instead "
                             "of tables")
    args = parser.parse_args(argv)

    if not args.snapshot_dir and not args.ingest_autoscale:
        parser.error("need a snapshot dir and/or --ingest-autoscale")

    h = History()
    if args.snapshot_dir and not h.load(args.snapshot_dir):
        sys.stderr.write(f"usage_report: no readable history.json under "
                         f"{args.snapshot_dir!r} (starting empty)\n")
    if args.ingest_autoscale:
        n = ingest_autoscale(h, args.ingest_autoscale)
        sys.stderr.write(f"usage_report: ingested {n} autoscale "
                         f"point(s)\n")
    if args.save:
        if not args.snapshot_dir:
            parser.error("--save needs a snapshot_dir to write to")
        h.save(args.snapshot_dir)

    report = build_report(h, res=args.res)
    if args.json:
        json.dump(report, sys.stdout, indent=2)
        sys.stdout.write("\n")
    else:
        render_text(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
