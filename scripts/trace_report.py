#!/usr/bin/env python
"""Merge per-peer span JSONL into one cross-peer round report.

Each peer's flight recorder (``dalle_tpu/obs``, wired via
``CollabConfig.trace_file`` / ``ServingConfig.trace_file`` or the soak
harnesses) appends spans whose trace ids are PROTOCOL ids — swarm round
ids (``{run}:grads:{epoch}``), state-transfer nonces, serving request
ids. Because the correlation key is the protocol id and not a clock,
this report needs no time synchronization: it merges any number of
per-peer files and answers the question the soak oracles cannot —
*which phase of which round on which peer stalled or diverged first*.

Outputs (printed table + ``--out`` JSON):

- **per-phase latency**: p50/p95/max duration per (plane, phase)
  across all rounds/requests;
- **straggler attribution**: for every (trace, phase) with >= 2 peers,
  the slowest peer; aggregated into a per-peer straggler count and the
  worst phase gap (slowest / median peer duration);
- **gap detection**: within one peer's own monotonic timeline, spans
  of the same trace separated by more than ``--gap-s`` of silence
  (span end -> next span start) — the signature of a stall the phase
  walls themselves don't show;
- **late steps**: the trainer's ``loop/late_step`` events (plane
  ``train``; ``dalle_tpu/obs/late.py``): which step ran over, by how
  much, in which phase, and the cause its record supports;
- **memory**: the trainer's ``memory/*`` events (``dalle_tpu/obs/
  memory.py``): what its trees hold of the device, where the process's
  peak rose, and what held a device near its limit or past it;
- **round table** (``--rounds``): one row per trace id with per-peer
  total span time, phase count, and errors.

Usage::

    python scripts/trace_report.py peer0.jsonl peer1.jsonl ...
    python scripts/trace_report.py --glob 'traces/*.jsonl' --out R.json
"""

from __future__ import annotations

import argparse
import glob as globlib
import json
import os
import sys
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from dalle_tpu.obs import memory as memory_account  # noqa: E402
from dalle_tpu.obs.late import LATE_EVENT, late_step_line  # noqa: E402
from dalle_tpu.obs.trace import load_jsonl, merge_rows  # noqa: E402


def _percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile without numpy (this tool must run
    on a box with nothing but the stdlib)."""
    if not values:
        return float("nan")
    vs = sorted(values)
    if len(vs) == 1:
        return vs[0]
    pos = (len(vs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vs) - 1)
    frac = pos - lo
    return vs[lo] * (1 - frac) + vs[hi] * frac


def phase_table(rows: List[dict]) -> Dict[str, dict]:
    """p50/p95/max duration per (plane, phase) over every span."""
    by_phase: Dict[str, List[float]] = {}
    for r in rows:
        if r.get("dur_s", 0) <= 0:
            continue  # events carry no duration
        by_phase.setdefault(f"{r['plane']}:{r['phase']}", []).append(
            float(r["dur_s"]))
    return {
        key: {"n": len(durs),
              "p50_s": round(_percentile(durs, 50.0), 6),
              "p95_s": round(_percentile(durs, 95.0), 6),
              "max_s": round(max(durs), 6)}
        for key, durs in sorted(by_phase.items())
    }


def straggler_attribution(rows: List[dict]) -> dict:
    """Per (trace, phase) with >= 2 participating peers: who was
    slowest, and by how much vs the median peer. Aggregated to a
    per-peer straggle count — the \"which peer drags every round\"
    answer."""
    cell: Dict[tuple, Dict[str, float]] = {}
    for r in rows:
        if r.get("dur_s", 0) <= 0:
            continue
        key = (r["trace"], r["plane"], r["phase"])
        peers = cell.setdefault(key, {})
        peer = str(r.get("peer", ""))
        peers[peer] = max(peers.get(peer, 0.0), float(r["dur_s"]))
    counts: Dict[str, int] = {}
    worst: Optional[dict] = None
    examined = 0
    for (trace, plane, phase), peers in cell.items():
        if len(peers) < 2:
            continue
        examined += 1
        slowest, t_slow = max(peers.items(), key=lambda kv: kv[1])
        med = _percentile(list(peers.values()), 50.0)
        counts[slowest] = counts.get(slowest, 0) + 1
        ratio = t_slow / med if med > 0 else float("inf")
        if worst is None or ratio > worst["ratio"]:
            worst = {"trace": trace, "plane": plane, "phase": phase,
                     "peer": slowest, "dur_s": round(t_slow, 6),
                     "median_s": round(med, 6),
                     "ratio": round(ratio, 3)}
    return {"cells_examined": examined,
            "straggles_by_peer": dict(sorted(
                counts.items(), key=lambda kv: -kv[1])),
            "worst": worst}


def detect_gaps(rows: List[dict], gap_s: float = 1.0) -> List[dict]:
    """Silent windows inside one peer's own timeline of one trace:
    consecutive spans (by that peer's monotonic t0) separated by more
    than ``gap_s`` between span end and next span start. Cross-peer
    t0s are never compared (clocks are per-peer)."""
    by_peer_trace: Dict[tuple, List[dict]] = {}
    for r in rows:
        by_peer_trace.setdefault(
            (str(r.get("peer", "")), r["trace"]), []).append(r)
    gaps: List[dict] = []
    for (peer, trace), spans in sorted(by_peer_trace.items()):
        spans.sort(key=lambda r: float(r.get("t0", 0.0)))
        for a, b in zip(spans, spans[1:]):
            end = float(a.get("t0", 0.0)) + float(a.get("dur_s", 0.0))
            silent = float(b.get("t0", 0.0)) - end
            if silent > gap_s:
                gaps.append({"peer": peer, "trace": trace,
                             "after_phase": a["phase"],
                             "before_phase": b["phase"],
                             "gap_s": round(silent, 6)})
    gaps.sort(key=lambda g: -g["gap_s"])
    return gaps


def round_table(rows: List[dict]) -> List[dict]:
    """One row per trace id: participating peers, per-peer total span
    wall, phase count, error spans."""
    by_trace: Dict[str, List[dict]] = {}
    for r in rows:
        by_trace.setdefault(r["trace"], []).append(r)
    out = []
    for trace, spans in sorted(by_trace.items()):
        peers: Dict[str, dict] = {}
        for r in spans:
            p = peers.setdefault(str(r.get("peer", "")),
                                 {"spans": 0, "total_s": 0.0,
                                  "errors": 0})
            p["spans"] += 1
            p["total_s"] = round(p["total_s"]
                                 + float(r.get("dur_s", 0.0)), 6)
            if (r.get("a") or {}).get("error"):
                p["errors"] += 1
        out.append({"trace": trace, "peers": peers})
    return out


def setup_facts(rows: List[dict]) -> Dict[str, dict]:
    """What the trainer's set-up rows say beside their duration (plane
    ``train``, trace ``setup``): e.g. ``setup/warmup``'s ``grad_reduction``,
    the plan the gradient step took for its sum over ``dp``, its
    ``attn_layout``, how many attention layers took the Mosaic kernel, and
    its ``layer_loop``, which slots of the layer scan run under a
    conditional."""
    facts: Dict[str, dict] = {}
    for r in rows:
        if r["plane"] == "train" and r["trace"] == "setup" and r.get("a") \
                and r.get("dur_s", 0) > 0:
            facts.setdefault(f"{r.get('peer', '')} {r['phase']}".strip(),
                             {}).update(r["a"])
    return facts


def late_steps(rows: List[dict]) -> List[dict]:
    """The trainer's late-step records (``loop/late_step`` events, one
    after the ``loop/step`` row of every step that ran over): peer, step
    and the record's attributes, with the line the trainer logged."""
    return [dict(r.get("a", {}), peer=str(r.get("peer", "")),
                 trace=r["trace"],
                 line=late_step_line(r["trace"], r.get("a", {})))
            for r in rows
            if r["plane"] == "train" and r["phase"] == LATE_EVENT]


def memory_events(rows: List[dict]) -> List[dict]:
    """The memory account's events (``memory/owners``, ``peak_rose``,
    ``near_limit``, ``exhausted``), each with its line."""
    return [dict(r.get("a", {}), peer=str(r.get("peer", "")),
                 phase=r["phase"], trace=r["trace"],
                 line=memory_account.event_line(r))
            for r in rows
            if r["plane"] == "train" and r["phase"].startswith("memory/")]


def build_report(files: List[str], gap_s: float = 1.0,
                 rounds: bool = False) -> dict:
    per_peer = [load_jsonl(f) for f in files]
    rows = merge_rows(per_peer)
    report = {
        "files": list(files),
        "spans": len(rows),
        "traces": len({r["trace"] for r in rows}),
        "peers": sorted({str(r.get("peer", "")) for r in rows}),
        "phases": phase_table(rows),
        "setup": setup_facts(rows),
        "stragglers": straggler_attribution(rows),
        "gaps": detect_gaps(rows, gap_s=gap_s),
        "late_steps": late_steps(rows),
        "memory": memory_events(rows),
    }
    if rounds:
        report["rounds"] = round_table(rows)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", help="per-peer span JSONL files")
    ap.add_argument("--glob", type=str, default=None,
                    help="glob for per-peer JSONL files (quoted)")
    ap.add_argument("--gap-s", type=float, default=1.0,
                    help="silent-window threshold for gap detection")
    ap.add_argument("--rounds", action="store_true",
                    help="include the per-round table in the report")
    ap.add_argument("--out", type=str, default=None,
                    help="write the full report JSON here")
    args = ap.parse_args(argv)
    files = list(args.files)
    if args.glob:
        files.extend(sorted(globlib.glob(args.glob)))
    if not files:
        ap.error("no input files (positional args or --glob)")

    report = build_report(files, gap_s=args.gap_s, rounds=args.rounds)

    print(f"{report['spans']} spans, {report['traces']} traces, "
          f"peers: {', '.join(report['peers'])}")
    print(f"{'phase':<28}{'n':>6}{'p50_s':>10}{'p95_s':>10}"
          f"{'max_s':>10}")
    for phase, st in report["phases"].items():
        print(f"{phase:<28}{st['n']:>6}{st['p50_s']:>10.4f}"
              f"{st['p95_s']:>10.4f}{st['max_s']:>10.4f}")
    for phase, attrs in report["setup"].items():
        print(f"  {phase}: " + ", ".join(
            f"{k}={v}" for k, v in sorted(attrs.items())))
    strag = report["stragglers"]
    if strag["straggles_by_peer"]:
        print(f"stragglers ({strag['cells_examined']} multi-peer "
              f"cells): {strag['straggles_by_peer']}")
        if strag["worst"]:
            w = strag["worst"]
            print(f"  worst: {w['peer']} on {w['phase']} of "
                  f"{w['trace']} — {w['dur_s']}s vs median "
                  f"{w['median_s']}s ({w['ratio']}x)")
    for g in report["gaps"][:8]:
        print(f"  gap: {g['peer']} went silent {g['gap_s']}s inside "
              f"{g['trace']} ({g['after_phase']} -> "
              f"{g['before_phase']})")
    for late in report["late_steps"]:
        print(f"  late step: {late['peer']} {late['line']}")
    for event in report["memory"]:
        print(f"  {event['phase']}: {event['peer']} {event['line']}")
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1)
            fh.write("\n")
        print(f"report: {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
