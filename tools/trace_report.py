"""Summarize distributed traces and crash flight bundles.

The span tracer (lightctr_tpu/obs/trace.py) leaves one JSONL span file per
process (``LIGHTCTR_TRACE_DIR``), and the flight recorder
(lightctr_tpu/obs/flight.py) leaves a crash bundle whose span section uses
the same record shape.  This tool merges any mix of them into one causal
view:

  python -m tools.trace_report TRACE.jsonl [MORE.jsonl ...|DIR]
      # -> per-phase critical-path summary (total / self time per span
      #    name), slowest-span table, cross-process stitch counts
  python -m tools.trace_report DIR --perfetto OUT.json
      # -> Chrome trace-event JSON: load in Perfetto (ui.perfetto.dev)
      #    or chrome://tracing; cross-process parent links drawn as
      #    flow arrows
  python -m tools.trace_report --flight BUNDLE.jsonl
      # -> flight-bundle postmortem: reason, registry snapshots, span
      #    ring and event ring summaries
  python -m tools.trace_report DIR --rounds [--epoch N]
      # -> hierarchical-exchange round timelines: for each (epoch,
      #    table) rendezvous round, every host's push arrival offset
      #    behind the first, pull-satisfied offsets, the straggler by
      #    name, and the round's critical path (first push -> last push
      #    -> last pull satisfied) stitched from the hier client/shard
      #    spans that share trace context over the wire

  python -m tools.trace_report SPANS.jsonl --stalls 50
      # -> for every ``serve/queue_wait`` of 50 ms or more: what the
      #    thread that popped the request (the scorer) was inside for
      #    that interval, by innermost span, and what no span covers

A directory argument expands to every ``trace-*.jsonl`` inside it (the
per-process files one run leaves behind).  Reads are tolerant of torn
tails — a crashed writer's half-line is skipped, not fatal.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from lightctr_tpu.obs import read_jsonl  # noqa: E402
from lightctr_tpu.obs.trace import to_chrome_trace  # noqa: E402


def _expand(paths: List[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(glob.glob(os.path.join(p, "trace-*.jsonl"))))
        else:
            out.append(p)
    return out


def load_spans(paths: List[str]) -> List[Dict]:
    """Collect span records from span JSONL files and/or flight bundles
    (both carry ``kind == "span"`` records), deduped by span id — the
    same span can appear in a stream file AND a crash bundle."""
    seen = set()
    spans: List[Dict] = []
    for path in _expand(paths):
        for rec in read_jsonl(path):
            if rec.get("kind") != "span" or "span" not in rec:
                continue
            if rec["span"] in seen:
                continue
            seen.add(rec["span"])
            spans.append(rec)
    spans.sort(key=lambda r: r.get("ts", 0.0))
    return spans


def summarize_spans(spans: List[Dict], top: int = 10) -> Dict:
    """Spans -> report: per-phase (span name) totals with SELF time — a
    span's duration minus its children's, the critical-path view that says
    where the time actually went — plus the slowest individual spans and
    how much of the tree crossed a process boundary."""
    by_id = {s["span"]: s for s in spans}
    child_time: Dict[str, float] = {}
    cross_process = 0
    orphans = 0
    for s in spans:
        parent = s.get("parent")
        if parent is None:
            continue
        p = by_id.get(parent)
        if p is None:
            orphans += 1  # parent outside the ring/file set
            continue
        child_time[parent] = child_time.get(parent, 0.0) + float(
            s.get("dur_s", 0.0))
        if p.get("pid") != s.get("pid"):
            cross_process += 1

    phases: Dict[str, Dict] = {}
    for s in spans:
        ph = phases.setdefault(s["name"], {
            "count": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0,
            "errors": 0,
        })
        dur = float(s.get("dur_s", 0.0))
        ph["count"] += 1
        ph["total_s"] += dur
        ph["self_s"] += max(0.0, dur - child_time.get(s["span"], 0.0))
        ph["max_s"] = max(ph["max_s"], dur)
        if "error" in s:
            ph["errors"] += 1
    for ph in phases.values():
        ph["mean_s"] = round(ph["total_s"] / ph["count"], 6)
        for k in ("total_s", "self_s", "max_s"):
            ph[k] = round(ph[k], 6)

    slowest = sorted(spans, key=lambda s: s.get("dur_s", 0.0),
                     reverse=True)[:top]
    report = {
        "spans": len(spans),
        "traces": len({s.get("trace") for s in spans}),
        "processes": sorted({s.get("pid") for s in spans}),
        "roots": sum(1 for s in spans if "parent" not in s),
        "cross_process_edges": cross_process,
        "orphan_parents": orphans,
        "phases": dict(sorted(phases.items(),
                              key=lambda kv: -kv[1]["self_s"])),
        "slowest": [
            {
                "name": s["name"], "dur_s": s.get("dur_s"),
                "pid": s.get("pid"), "trace": s.get("trace"),
                "span": s.get("span"),
                **({"attrs": s["attrs"]} if "attrs" in s else {}),
            }
            for s in slowest
        ],
    }
    if spans:
        ts = [s["ts"] for s in spans if "ts" in s]
        if ts:
            report["span_window_s"] = round(max(ts) - min(ts), 3)
    return report


def summarize_rounds(spans: List[Dict], epoch=None) -> Dict:
    """Hier client spans -> per-(epoch, table) round timelines.  Every
    ``hier_client/push*`` span carries ``epoch``/``table``/``host``
    attrs (dist/hier.py), so one merged span set from all hosts yields,
    per round: each host's push arrival offset behind the round's FIRST
    push (the wait it charged the round with), its pull-satisfied
    offset, the straggler by name, and the critical path.  Chunked
    pushes (the streaming rendezvous, ISSUE 16) emit one
    ``hier_client/push_chunk`` span per transmitted window — each host's
    entry then carries the per-chunk timeline (first/last chunk offsets
    and count), separating a late STARTER from a slow TRICKLER.
    Shard-side ``hier/push|pull`` spans stitch under these via the wire
    trace context (counted here as ``shard_spans``)."""
    rounds: Dict = {}
    shard_spans = 0
    for s in spans:
        name = s.get("name", "")
        if name in ("hier/push", "hier/pull"):
            shard_spans += 1
            continue
        if name not in ("hier_client/push", "hier_client/push_group",
                        "hier_client/push_chunk",
                        "hier_client/pull", "hier_client/pull_group"):
            continue
        attrs = s.get("attrs") or {}
        ep = attrs.get("epoch")
        if ep is None or (epoch is not None and int(ep) != int(epoch)):
            continue
        key = (int(ep), attrs.get("table", "group"))
        r = rounds.setdefault(key, {"hosts": {}})
        host = str(attrs.get("host", s.get("pid", "?")))
        h = r["hosts"].setdefault(host, {})
        if name == "hier_client/push_chunk":
            # the transmit instant of ONE chunk window (worker-thread
            # side): the per-chunk timeline of this host's contribution
            h.setdefault("chunk_ts", []).append(
                (int(attrs.get("chunk", 0)), float(s.get("ts", 0.0)))
            )
        elif name.startswith("hier_client/push"):
            # first push per host wins (a retried frame keeps the
            # original arrival)
            h.setdefault("push_ts", float(s.get("ts", 0.0)))
        else:
            h["pull_done_ts"] = (float(s.get("ts", 0.0))
                                 + float(s.get("dur_s", 0.0)))
    out = []
    for (ep, table) in sorted(rounds, key=lambda k: (k[0], str(k[1]))):
        r = rounds[(ep, table)]
        pushes = {h: v["push_ts"] for h, v in r["hosts"].items()
                  if "push_ts" in v}
        if not pushes:
            continue
        t0 = min(pushes.values())
        first = min(pushes, key=pushes.get)
        straggler = max(pushes, key=pushes.get)
        spread = pushes[straggler] - t0
        hosts = {}
        for h, v in sorted(r["hosts"].items()):
            e: Dict = {}
            if "push_ts" in v:
                e["push_offset_s"] = round(v["push_ts"] - t0, 6)
            if "pull_done_ts" in v:
                e["pull_done_offset_s"] = round(v["pull_done_ts"] - t0, 6)
            if "chunk_ts" in v:
                cts = [ts for _, ts in v["chunk_ts"]]
                e["chunks"] = len(v["chunk_ts"])
                e["first_chunk_offset_s"] = round(min(cts) - t0, 6)
                e["last_chunk_offset_s"] = round(max(cts) - t0, 6)
                e["chunk_spread_s"] = round(max(cts) - min(cts), 6)
            hosts[h] = e
        entry: Dict = {
            "epoch": ep, "table": table, "hosts": hosts,
            "straggler": straggler,
            "arrival_spread_s": round(spread, 6),
        }
        pulls = [v["pull_done_ts"] for v in r["hosts"].values()
                 if "pull_done_ts" in v]
        if pulls:
            done = max(pulls) - t0
            entry["round_done_offset_s"] = round(done, 6)
            entry["critical_path"] = [
                {"event": "first_push", "host": first, "offset_s": 0.0},
                {"event": "last_push", "host": straggler,
                 "offset_s": round(spread, 6)},
                {"event": "last_pull_satisfied",
                 "offset_s": round(done, 6)},
            ]
        out.append(entry)
    report: Dict = {"rounds": out, "count": len(out),
                    "shard_spans": shard_spans}
    if out:
        worst = max(out, key=lambda r: r["arrival_spread_s"])
        report["worst_round"] = {
            "epoch": worst["epoch"], "table": worst["table"],
            "straggler": worst["straggler"],
            "arrival_spread_s": worst["arrival_spread_s"],
        }
    return report


#: the interval ``--stalls`` explains: recorded by the thread that ends it
STALL_SPAN = "serve/queue_wait"


def _interval_ns(s: Dict):
    """A span's ``(start, end)`` in ns (older records carry only
    ``ts`` / ``dur_s``)."""
    if "start_ns" in s:
        return int(s["start_ns"]), int(s["end_ns"])
    start = int(float(s.get("ts", 0.0)) * 1e9)
    return start, start + int(float(s.get("dur_s", 0.0)) * 1e9)


def summarize_stalls(spans: List[Dict], min_ms: float, top: int = 10) -> Dict:
    """For every ``serve/queue_wait`` of at least ``min_ms``: what the
    thread that recorded it — the scorer, which popped the request — was
    inside during the wait.  Each moment counts for the INNERMOST span
    open on that thread (a span's time less its children's, both cut to
    the interval); what no span covers is ``(no span)``: the thread was
    in no instrumented region, i.e. between spans or not scheduled."""
    import bisect

    threads: Dict = {}
    for s in spans:
        if s["name"] != STALL_SPAN:
            threads.setdefault((s.get("pid"), s.get("tid")), []).append(
                _interval_ns(s) + (s,))
    index = {}
    for key, rows in threads.items():
        rows.sort(key=lambda r: r[0])
        index[key] = (rows, [r[0] for r in rows],
                      max(r[1] - r[0] for r in rows))

    def inside(key, t0: int, t1: int):
        rows, starts, longest = index.get(key, ([], [], 0))
        lo = bisect.bisect_left(starts, t0 - longest)
        hi = bisect.bisect_right(starts, t1)
        cut = {}        # span id -> (span, ns of it inside [t0, t1])
        for a, b, s in rows[lo:hi]:
            part = min(b, t1) - max(a, t0)
            if part > 0:
                cut[s["span"]] = (s, part)
        own = {k: part for k, (_, part) in cut.items()}
        covered = 0
        for sid, (s, part) in cut.items():
            if s.get("parent") in own:
                own[s["parent"]] -= part
            else:
                covered += part
        by_name: Dict[str, float] = {}
        for sid, ns in own.items():
            name = cut[sid][0]["name"]
            by_name[name] = by_name.get(name, 0.0) + max(0, ns) / 1e6
        by_name["(no span)"] = max(0, (t1 - t0) - covered) / 1e6
        longest_in = sorted(cut.values(), key=lambda c: -c[1])[:3]
        return by_name, [
            {"name": s["name"], "dur_ms": round(float(s["dur_s"]) * 1e3, 3),
             **({"attrs": s["attrs"]} if "attrs" in s else {})}
            for s, _ in longest_in]

    stalls, held_ms, held_n = [], {}, {}
    waits = [s for s in spans if s["name"] == STALL_SPAN]
    for w in waits:
        t0, t1 = _interval_ns(w)
        wait_ms = (t1 - t0) / 1e6
        if wait_ms < min_ms:
            continue
        by_name, longest = inside((w.get("pid"), w.get("tid")), t0, t1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
        for name, ms in ranked:
            held_ms[name] = held_ms.get(name, 0.0) + ms
        held_n[ranked[0][0]] = held_n.get(ranked[0][0], 0) + 1
        stalls.append({
            "trace": w.get("trace"), "wait_ms": round(wait_ms, 3),
            "ts": w.get("ts"), "batch": (w.get("attrs") or {}).get("batch"),
            "held_by": ranked[0][0],
            "inside_ms": {k: round(v, 3) for k, v in ranked if v > 0},
            "longest_spans": longest,
        })
    stalls.sort(key=lambda r: -r["wait_ms"])
    return {
        "stall_span": STALL_SPAN, "min_ms": min_ms,
        "waits": len(waits), "stalls": len(stalls),
        # how many stalls each span held the largest part of, and the
        # milliseconds of all stalls spent inside each
        "held_by": dict(sorted(held_n.items(), key=lambda kv: -kv[1])),
        "inside_ms": {k: round(v, 3) for k, v in
                      sorted(held_ms.items(), key=lambda kv: -kv[1]) if v > 0},
        "worst": stalls[:top],
    }


def summarize_flight(path: str) -> Dict:
    """Flight bundle -> postmortem report."""
    recs = read_jsonl(path)
    header = next((r for r in recs if r.get("kind") == "flight"), {})
    spans = [r for r in recs if r.get("kind") == "span"]
    events = [r["record"] for r in recs
              if r.get("kind") == "flight_event" and "record" in r]
    metrics = [r for r in recs if r.get("kind") == "metrics"]
    health = [r for r in recs if r.get("kind") == "health"]
    event_kinds: Dict[str, int] = {}
    for e in events:
        k = e.get("kind", "?")
        event_kinds[k] = event_kinds.get(k, 0) + 1
    report = {
        "bundle": path,
        "reason": header.get("reason"),
        "ts": header.get("ts"),
        "pid": header.get("pid"),
        "argv": header.get("argv"),
        "registries": {
            m.get("registry", "?"): {
                "counters": len(m.get("snapshot", {}).get("counters", {})),
                "gauges": len(m.get("snapshot", {}).get("gauges", {})),
                "histograms": len(
                    m.get("snapshot", {}).get("histograms", {})),
            }
            for m in metrics
        },
        "span_ring": summarize_spans(spans, top=5) if spans
        else {"spans": 0},
        "event_ring": {
            "events": len(events),
            "by_kind": dict(sorted(event_kinds.items())),
            "last": events[-3:],
        },
    }
    if health:
        # the health plane's verdicts at dump time: which detector put the
        # bundle on disk (anomaly-triggered dumps carry a health: reason)
        report["health"] = {
            h.get("component", "?"): {
                "status": h.get("verdict", {}).get("status"),
                "detectors": {
                    name: {k: d.get(k) for k in ("status", "detail")
                           if k in d}
                    for name, d in h.get("verdict", {})
                    .get("detectors", {}).items()
                },
            }
            for h in health
        }
    # model-quality sketches ride the bundle as extra registries named
    # quality:<component> whose snapshots self-mark with "quality": True
    # — a calibration/AUC/drift postmortem needs the sketch state AT the
    # dump, not whatever the live process has rolled to since
    quality = {
        m.get("registry", "?"): m.get("snapshot", {})
        for m in metrics
        if m.get("snapshot", {}).get("quality")
    }
    if quality:
        report["quality"] = quality
    # the resource plane rides the same way: registries named
    # resources:<component> (compile trackers) whose snapshots self-mark
    # with "resources": True — the jit-cache/queue/memory state AT the
    # dump is what a recompile-storm or saturation postmortem reads
    resources = {
        m.get("registry", "?"): m.get("snapshot", {})
        for m in metrics
        if m.get("snapshot", {}).get("resources")
    }
    if resources:
        report["resources"] = resources
    # the device plane too: registries named device:<component> (program
    # catalogs, live-buffer censuses, donation watches, the profiler
    # trigger) whose snapshots self-mark with "device": True — an
    # hbm-pressure or donation postmortem reads the census/roofline state
    # AT the dump
    device = {
        m.get("registry", "?"): m.get("snapshot", {})
        for m in metrics
        if m.get("snapshot", {}).get("device")
    }
    if device:
        report["device"] = device
    # surface the headline counters — the numbers a postmortem reads first
    for m in metrics:
        c = m.get("snapshot", {}).get("counters", {})
        picked = {k: v for k, v in c.items() if k in (
            "trainer_steps_total", "ps_protocol_errors_total",
            "master_queued_decisions_total", "ps_store_gated_pulls_total",
        )}
        if picked:
            report.setdefault("headline_counters", {})[
                m.get("registry", "?")] = picked
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help="span JSONL files, flight bundles, or directories "
                         "of trace-*.jsonl")
    ap.add_argument("--perfetto", metavar="OUT_JSON",
                    help="also write a Chrome trace-event / Perfetto JSON")
    ap.add_argument("--flight", metavar="BUNDLE",
                    help="summarize a flight-recorder bundle instead")
    ap.add_argument("--rounds", action="store_true",
                    help="per-round hierarchical-exchange timelines: host "
                         "arrival offsets, straggler, critical path")
    ap.add_argument("--stalls", type=float, metavar="MS", default=None,
                    help="for every serve/queue_wait of MS or more: what "
                         "the scorer thread was inside meanwhile")
    ap.add_argument("--epoch", type=int, default=None,
                    help="with --rounds: only this rendezvous epoch")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest-span table length (default 10)")
    ap.add_argument("--out", help="write the report JSON here too")
    args = ap.parse_args(argv)

    if args.flight:
        report = summarize_flight(args.flight)
    elif args.rounds:
        if not args.paths:
            ap.error("--rounds needs span JSONL paths/directories")
        report = summarize_rounds(load_spans(args.paths), epoch=args.epoch)
    elif args.stalls is not None:
        if not args.paths:
            ap.error("--stalls needs span JSONL paths/directories")
        report = summarize_stalls(load_spans(args.paths), args.stalls,
                                  top=args.top)
    else:
        if not args.paths:
            ap.error("give span JSONL paths/directories, or --flight BUNDLE")
        spans = load_spans(args.paths)
        report = summarize_spans(spans, top=args.top)
        if args.perfetto:
            with open(args.perfetto, "w") as f:
                json.dump(to_chrome_trace(spans), f)
            report["perfetto"] = args.perfetto

    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
