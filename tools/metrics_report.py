"""Summarize a telemetry run: JSONL event log -> one report JSON, a
registry snapshot -> Prometheus text, or health events -> verdict
timeline.

The obs layer (lightctr_tpu/obs/) leaves two artifacts behind: the JSONL
event log (``obs.configure_event_log(path=...)``) and registry snapshots
(scraped over the PS ``stats`` wire op or taken in-process).  This tool
turns either into something readable:

  python -m tools.metrics_report run.jsonl [--out REPORT.json]
      # -> per-kind event counts, step-time percentiles, exchanged-bytes
      #    totals, failover timeline
  python -m tools.metrics_report --prom snapshot.json
      # -> Prometheus text exposition of a registry snapshot (the JSON a
      #    shard's stats()["telemetry"] returns, or a merge of several)
  python -m tools.metrics_report --health RUN_DIR_or_FILE
      # -> health-plane report: transition timeline across every *.jsonl
      #    in a directory (one per process), final verdict per
      #    component/detector, anomaly-triggered flight bundles
  python -m tools.metrics_report --serve STATS_OR_SNAPSHOT_JSON
      # -> serving-plane report from a PredictionServer stats() dump (or
      #    a bare registry snapshot): request/latency percentiles from
      #    the serve histograms, shed totals by reason, micro-batch fill,
      #    cache hit rate
  python -m tools.metrics_report --store STATS_JSON
      # -> store-occupancy report from a PS stats() dump (one shard's
      #    dict or a ShardedPSClient list): rows / capacity / load
      #    factor / bytes resident for FLAT stores, plus per-tier
      #    occupancy, hit/fault/demotion counters, and fault-path
      #    latency for TIERED stores
  python -m tools.metrics_report --kernels SNAPSHOT_JSON
      # -> which implementation of the registered kernels (payload
      #    pack, flash attention) actually ran
      #    (trainer_kernel_path_total{phase,impl} from a registry
      #    snapshot or stats() dump): per-phase dispatch counts for
      #    pallas / interpret / xla — measured, not assumed; and per
      #    table how the sized apply engaged (trainer_apply_*_total),
      #    and whether the step or the host counted the ids
      #    (trainer_health_signals_total{source})
  python -m tools.metrics_report --online SNAPSHOT_JSON
      # -> online learning plane (docs/ONLINE.md): freshness age +
      #    per-entry apply-age percentiles, deltas applied vs
      #    degraded-to-full-refresh by reason, model hot-swap
      #    attempts/refusals, continuous-trainer step/export counters
  python -m tools.metrics_report --cluster MEMBERS_JSON
      # -> cluster straggler report (docs/OBSERVABILITY.md "Cluster
      #    rollup"): hosts ranked by rendezvous round-wait contribution
      #    (hier_round_wait_seconds{host=...}), members by step-time
      #    skew, scrape-down members listed — from a ClusterRollup
      #    members() dump, a {member: stats-or-snapshot} map, or a
      #    ShardedPSClient.stats() list
  python -m tools.metrics_report --quality SNAPSHOT_JSON
      # -> model-quality report (docs/OBSERVABILITY.md "Model-quality
      #    plane"): per-component streaming calibration ratio,
      #    sketch-AUC, logloss EWMA vs frozen baseline, per-field drift
      #    scores, feature-coverage totals, worst-drift pointer
  python -m tools.metrics_report --resources SNAPSHOT_JSON
      # -> resource/saturation report (docs/OBSERVABILITY.md "Resource &
      #    saturation plane"): per-fn jit compile counts + live cache
      #    ladders, per-queue depth/capacity/fill with queued-wait
      #    percentiles, memory bytes vs budgets, fullest-queue pointer
  python -m tools.metrics_report --device SNAPSHOT_JSON
      # -> device/compiled-program report (docs/OBSERVABILITY.md "Device
      #    plane"): per-program FLOPs, bytes accessed, arithmetic
      #    intensity, roofline utilization + memory breakdown, step-time
      #    percentiles, live-buffer census vs budgets, donation
      #    check/miss counters, profiler capture/refusal totals
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from lightctr_tpu.obs import read_jsonl, render_prometheus  # noqa: E402
from lightctr_tpu.obs.registry import histogram_quantile  # noqa: E402


def _percentiles(values):
    a = np.asarray(values, np.float64)
    return {
        "mean_s": round(float(a.mean()), 6),
        "p50_s": round(float(np.percentile(a, 50)), 6),
        "p95_s": round(float(np.percentile(a, 95)), 6),
        "p99_s": round(float(np.percentile(a, 99)), 6),
        "max_s": round(float(a.max()), 6),
    }


def summarize(records) -> dict:
    """Event records -> run report (exact percentiles: unlike the registry
    histograms these come from the raw per-step durations in the log)."""
    by_kind: dict = {}
    for r in records:
        by_kind.setdefault(r.get("kind", "?"), []).append(r)

    report: dict = {
        "events": len(records),
        "by_kind": {k: len(v) for k, v in sorted(by_kind.items())},
        "schema_versions": sorted(
            {r.get("v") for r in records} - {None}
        ),
    }
    ts = [r["ts"] for r in records if "ts" in r]
    if ts:
        report["span_s"] = round(max(ts) - min(ts), 3)

    steps = by_kind.get("step", [])
    if steps:
        durations = [s["duration_s"] for s in steps if "duration_s" in s]
        step_rep = {
            "count": len(steps),
            "examples_total": sum(s.get("examples", 0) for s in steps),
        }
        if durations:
            step_rep["step_time"] = _percentiles(durations)
        sparse_b = sum(s.get("sparse_exchange_bytes", 0) for s in steps)
        rs_b = sum(s.get("sparse_rs_bytes", 0) for s in steps)
        dense_b = sum(s.get("dense_ring_bytes", 0) for s in steps)
        if sparse_b or rs_b or dense_b:
            step_rep["sparse_exchange_bytes_total"] = sparse_b
            step_rep["sparse_rs_bytes_total"] = rs_b
            step_rep["dense_ring_bytes_total"] = dense_b
        report["steps"] = step_rep

    epochs = by_kind.get("epoch", [])
    if epochs:
        losses = [e["loss"] for e in epochs if "loss" in e]
        report["epochs"] = {
            "count": len(epochs),
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
        }

    exchanges = by_kind.get("exchange", [])
    if exchanges:
        report["exchange_decisions"] = [
            {k: e[k] for k in ("table", "policy", "bytes_per_step",
                               "fallback")
             if k in e}
            for e in exchanges
        ]

    failovers = by_kind.get("failover", [])
    if failovers:
        report["failovers"] = [
            {k: v for k, v in f.items() if k not in ("v",)}
            for f in failovers
        ]
    health = by_kind.get("health", [])
    if health:
        report["health"] = summarize_health(health)
    return report


def _expand_jsonl(path: str):
    """A directory expands to every ``*.jsonl`` inside it (the per-process
    event logs one run leaves behind); a file is itself."""
    if os.path.isdir(path):
        return sorted(glob.glob(os.path.join(path, "*.jsonl")))
    return [path]


def summarize_health(records) -> dict:
    """``health`` events -> transition timeline + final verdict per
    component/detector (the aggregate rows use the pseudo-detector name
    ``aggregate``) + any anomaly-triggered flight bundles."""
    health = sorted(
        (r for r in records if r.get("kind") == "health"),
        key=lambda r: r.get("ts", 0.0),
    )
    timeline = []
    final: dict = {}
    dumps = []
    for r in health:
        comp = r.get("component", "?")
        det = r.get("detector", "?")
        entry = {
            "ts": r.get("ts"), "component": comp, "detector": det,
            "from": r.get("prev"), "to": r.get("status"),
        }
        if r.get("detail"):
            entry["detail"] = r["detail"]
        timeline.append(entry)
        comp_final = final.setdefault(comp, {})
        if det == "aggregate":
            comp_final["status"] = r.get("status")
        else:
            comp_final.setdefault("detectors", {})[det] = r.get("status")
        if r.get("flight_bundle"):
            dumps.append({"ts": r.get("ts"), "component": comp,
                          "bundle": r["flight_bundle"]})
    report = {
        "transitions": len(timeline),
        "timeline": timeline,
        "final": final,
    }
    if dumps:
        report["flight_dumps"] = dumps
    return report


def _hist_summary(hist, unit_ms: bool = True) -> dict:
    """Registry histogram dict -> {count, p50, p99} via the standard
    bucket-interpolation estimator (obs.registry.histogram_quantile)."""
    scale = 1e3 if unit_ms else 1.0
    suffix = "_ms" if unit_ms else ""
    out = {"count": hist.get("count", 0)}
    if out["count"]:
        out[f"p50{suffix}"] = round(histogram_quantile(hist, 0.5) * scale, 3)
        out[f"p99{suffix}"] = round(histogram_quantile(hist, 0.99) * scale, 3)
        out[f"mean{suffix}"] = round(
            hist.get("sum", 0.0) / out["count"] * scale, 3)
    return out


def summarize_serve(doc: dict) -> dict:
    """A PredictionServer ``stats()`` dump (or a bare registry snapshot)
    -> serving report: latency/batch-fill percentiles from the serve
    histograms, shed totals by reason, cache counters."""
    snap = doc.get("telemetry", doc)
    counters = snap.get("counters", {})
    hists = snap.get("histograms", {})
    report: dict = {}
    requests = {
        k.split('op="', 1)[1].rstrip('"}'): v
        for k, v in counters.items()
        if k.startswith("serve_requests_total{")
    }
    if requests:
        report["requests"] = requests
    for name, key in (("predict_latency", "serve_predict_seconds"),
                      ("score_time", "serve_score_seconds")):
        if key in hists:
            report[name] = _hist_summary(hists[key])
    if "serve_batch_rows" in hists:
        h = hists["serve_batch_rows"]
        fill = {"count": h["count"]}
        if h["count"]:
            fill["mean_rows"] = round(h["sum"] / h["count"], 2)
            fill["p50_rows"] = round(histogram_quantile(h, 0.5), 1)
        report["batch_fill"] = fill
    shed = {
        k.split('reason="', 1)[1].rstrip('"}'): v
        for k, v in counters.items()
        if k.startswith("serve_shed_total{")
    }
    rows_total = counters.get("serve_rows_total", 0)
    shed_rows = counters.get("serve_shed_rows_total", 0)
    if shed or rows_total:
        report["shed"] = {
            "by_reason": shed,
            "rows": shed_rows,
            "rows_total": rows_total,
            "shed_frac": round(shed_rows / rows_total, 4)
            if rows_total else 0.0,
        }
    cache = doc.get("cache")
    if cache is None:
        # bare snapshot: rebuild the cache section from its counters
        hits = counters.get("serve_cache_hits_total", 0)
        misses = counters.get("serve_cache_misses_total", 0)
        if hits or misses:
            cache = {
                "hits": hits, "misses": misses,
                "hit_rate": round(hits / (hits + misses), 5)
                if hits + misses else 0.0,
                "invalidations": counters.get(
                    "serve_cache_invalidations_total", 0),
            }
    if cache:
        report["cache"] = cache
    if "health" in doc:
        report["health"] = {
            "status": doc["health"].get("status"),
            "latency_slo": (doc["health"].get("detectors") or {})
            .get("latency_slo", {}).get("status"),
        }
    return report


def summarize_store(doc) -> dict:
    """PS ``stats()`` dump(s) -> store-occupancy report.  Accepts ONE
    shard's stats dict or the list :meth:`ShardedPSClient.stats` returns
    (down shards stay visible).  Flat and tiered stores share the
    ``store`` section shape, so one dashboard covers both; a tiered shard
    additionally reports per-tier occupancy and — when its telemetry
    snapshot rides along — the tier-transition counters and fault-path
    latency percentiles declared in ``embed.tiered.TIER_SERIES``."""
    shards = doc if isinstance(doc, list) else [doc]
    out_shards = []
    totals = {"rows": 0, "bytes_resident": 0}
    for i, st in enumerate(shards):
        # prefer the REAL member id the sharded client stamps: under
        # elastic membership the list holds only live members, so the
        # enumerate position diverges from shard ids once any shard dies
        entry: dict = {"shard": int(st.get("shard", i))}
        if st.get("addr"):
            entry["addr"] = st["addr"]
        if st.get("down"):
            entry["down"] = True
            entry["error"] = st.get("error")
            out_shards.append(entry)
            continue
        store = st.get("store")
        if store is None:
            entry["error"] = "stats carry no store section (old server?)"
            out_shards.append(entry)
            continue
        entry.update(store)
        totals["rows"] += int(store.get("rows", 0))
        totals["bytes_resident"] += int(store.get("bytes_resident", 0))
        if "ledger" in st:
            entry["ledger"] = st["ledger"]
        snap = st.get("telemetry") or {}
        counters = snap.get("counters", {})
        tiered = {k: v for k, v in counters.items()
                  if k.startswith("tiered_")}
        if tiered:
            entry["tier_counters"] = tiered
            hits = tiered.get("tiered_hot_hits_total", 0)
            faults = (tiered.get("tiered_warm_faults_total", 0)
                      + tiered.get("tiered_cold_faults_total", 0)
                      + tiered.get("tiered_creates_total", 0))
            if hits + faults:
                entry["hot_hit_rate"] = round(hits / (hits + faults), 5)
        hists = snap.get("histograms", {})
        if "tiered_fault_seconds" in hists:
            entry["fault_latency"] = _hist_summary(
                hists["tiered_fault_seconds"])
        out_shards.append(entry)
    return {"shards": out_shards, "totals": totals}


def summarize_kernels(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> per-phase dispatch report of the registered
    kernels (``sparse_kernels.KERNELS``: the phases with two
    implementations): how many traces resolved each implementation of
    ``trainer_kernel_path_total``.  The counter increments once per
    dispatch at trace time (the pick is static inside jit), so this
    answers "which implementation actually ran".  Beside it, per table,
    how the sized apply engaged:
    ``trainer_apply_live_rows_total`` over ``trainer_apply_slots_total``
    is the share of the slots it worked on that held a live row (the rest
    is the rung's round-up; 1 - slots / ids is what the ladder spared).
    Where a table's rows are sharded over a mesh axis the two counters
    carry a ``shard`` label — each shard takes the rung its own rows
    need — and the table's entry lists ``shards`` beside its sums.  A
    table the trainer keeps in a fused store with its accumulator also
    counts the lane rows its apply writes
    (``trainer_apply_lane_rows_total``): over the live rows that is
    ``lane_row_share``, 1 where no two live rows share a lane row and 2/r
    where every one is shared in full.
    ``trainer_apply_scatter_slots_total`` over the slots is
    ``scatters_per_slot``: 1.0 where one scatter writes table and
    accumulator (the fused store), 2.0 where each has its own.
    ``health_signals`` says
    where those counts and the skew detector's ``table_touch`` came from
    (``trainer_health_signals_total{source}``): steps read off the step's
    own health vector (``device``) against steps the host counted with
    ``np.unique`` (``host``: the hybrid and hier exchange steps)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    phases: dict = {}
    total_by_impl: dict = {}
    apply: dict = {}
    signals: dict = {}

    def _labels(name: str, prefix: str) -> dict:
        return dict(
            part.split("=", 1)
            for part in name[len(prefix):-1].replace('"', "").split(",")
        )

    prefix = "trainer_kernel_path_total{"
    for name, val in counters.items():
        p = "trainer_health_signals_total{"
        if name.startswith(p):
            signals[_labels(name, p).get("source", "?")] = int(val)
        for what in ("live_rows", "slots", "scatter_slots", "lane_rows"):
            p = f"trainer_apply_{what}_total{{"
            if name.startswith(p):
                labels = _labels(name, p)
                entry = apply.setdefault(labels.get("table", "?"), {})
                entry[what] = entry.get(what, 0) + int(val)
                if "shard" in labels:
                    entry.setdefault("shards", {}).setdefault(
                        labels["shard"], {})[what] = int(val)
        if not name.startswith(prefix):
            continue
        labels = _labels(name, prefix)
        phase = labels.get("phase", "?")
        impl = labels.get("impl", "?")
        phases.setdefault(phase, {})[impl] = \
            phases.get(phase, {}).get(impl, 0) + int(val)
        total_by_impl[impl] = total_by_impl.get(impl, 0) + int(val)
    for table in apply.values():
        if "shards" in table:
            table["shards"] = dict(sorted(table["shards"].items()))
        for entry in (table, *table.get("shards", {}).values()):
            if entry.get("slots"):
                entry["live_share"] = round(
                    entry.get("live_rows", 0) / entry["slots"], 4)
                if "scatter_slots" in entry:
                    entry["scatters_per_slot"] = round(
                        entry["scatter_slots"] / entry["slots"], 4)
            if "lane_rows" in entry and entry.get("live_rows"):
                entry["lane_row_share"] = round(
                    entry["lane_rows"] / entry["live_rows"], 4)
    return {
        "apply": dict(sorted(apply.items())),
        "health_signals": dict(sorted(signals.items())),
        "phases": {p: dict(sorted(v.items())) for p, v in
                   sorted(phases.items())},
        "dispatches_by_impl": dict(sorted(total_by_impl.items())),
        "fused_active": bool(total_by_impl.get("pallas", 0)
                             + total_by_impl.get("interpret", 0)),
    }


def summarize_exchange(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> gradient-exchange report: per-table algorithm
    decisions (``trainer_exchange_algo_total{table,algo}`` — dense ring,
    sparse allgather, sparse reduce-scatter, or the HIERARCHICAL
    two-level exchange), per-table bytes (a policy each; of the
    one-program mesh step its two joins, ``rows_join`` and ``grad_join``:
    bytes a member hands each ``psum``), the per-algorithm byte totals,
    and for the hierarchical path its per-HOP split: the ICI local-merge
    bytes vs the DCN wire bytes (the number that stays flat in local
    replica count — docs/SPARSE_EXCHANGE.md)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})

    def _labeled(prefix):
        out = {}
        p = prefix + "{"
        for name, val in counters.items():
            if not name.startswith(p):
                continue
            labels = dict(
                part.split("=", 1)
                for part in name[len(p):-1].replace('"', "").split(",")
            )
            out[tuple(sorted(labels.items()))] = int(val)
        return out

    tables: dict = {}
    for labels, val in _labeled("trainer_exchange_algo_total").items():
        ld = dict(labels)
        t = tables.setdefault(ld.get("table", "?"), {"algo_steps": {}})
        t["algo_steps"][ld.get("algo", "?")] = val
    for labels, val in _labeled("trainer_exchange_bytes_total").items():
        ld = dict(labels)
        t = tables.setdefault(ld.get("table", "?"), {"algo_steps": {}})
        t.setdefault("bytes", {})[ld.get("policy", "?")] = val
    totals = {
        "sparse_allgather": counters.get(
            "trainer_sparse_exchange_bytes_total", 0),
        "sparse_rs": counters.get("trainer_sparse_rs_bytes_total", 0),
        "dense_ring": counters.get("trainer_dense_ring_bytes_total", 0),
        "hier_wire": counters.get("trainer_hier_wire_bytes_total", 0),
        "hier_local": counters.get("trainer_hier_local_bytes_total", 0),
    }
    report = {
        "tables": {k: tables[k] for k in sorted(tables)},
        "bytes_by_algo": totals,
        "rs_fallback_steps": counters.get("trainer_rs_fallback_total", 0),
        "rs_overflow_entries": counters.get("trainer_rs_overflow_total", 0),
        "hier_active": bool(totals["hier_wire"]),
    }
    if totals["hier_wire"]:
        # the hierarchy's reason to exist, as a single number: how many
        # ICI bytes were merged down to each DCN byte
        report["hier_local_to_wire_x"] = round(
            totals["hier_local"] / max(totals["hier_wire"], 1), 3)
    # wire-codec honesty (ISSUE 13): measured socket bytes vs the fp32
    # equivalent of the identical payload, the id bytes the shared
    # streams never shipped, and the undelivered EF residual mass — the
    # compression claim as measured numbers, not model assumptions
    packed = counters.get("trainer_hier_wire_packed_bytes_total", 0)
    fp32_eq = counters.get("trainer_hier_wire_fp32_bytes_total", 0)
    id_saved = counters.get("trainer_hier_wire_id_saved_bytes_total", 0)
    gauges = snap.get("gauges", {})
    if packed or fp32_eq or id_saved:
        codec = {
            "packed_bytes": packed,
            "fp32_equiv_bytes": fp32_eq,
            "shared_id_saved_bytes": id_saved,
        }
        if packed:
            codec["compression_x"] = round(fp32_eq / packed, 3)
            # how much bigger the wire would be had every table shipped
            # its own id stream
            codec["shared_id_dedup_x"] = round(
                (packed + id_saved) / packed, 3)
        if "trainer_hier_wire_ef_mass" in gauges:
            codec["ef_residual_mass"] = round(
                gauges["trainer_hier_wire_ef_mass"], 6)
        report["wire_codec"] = codec
    # streaming rendezvous (ISSUE 16): chunk fill — rows shipped over
    # rows the dispatched windows could hold (near-empty windows waste
    # frame headers) — and overlap ratio — the share of the push wall
    # the dispatch/commit ticket hid under compute
    chunk_pushes = counters.get("trainer_hier_chunk_pushes_total", 0)
    chunk_rows = counters.get("trainer_hier_chunk_rows_total", 0)
    chunk_cap = counters.get("trainer_hier_chunk_capacity_rows_total", 0)
    push_s = counters.get("trainer_hier_overlap_push_seconds_total", 0)
    blocked_s = counters.get(
        "trainer_hier_overlap_blocked_seconds_total", 0)
    if chunk_pushes:
        streaming = {
            "chunk_pushes": chunk_pushes,
            "chunk_rows": chunk_rows,
            "chunk_fill": round(chunk_rows / max(chunk_cap, 1), 3),
            "push_seconds": round(float(push_s), 6),
            "blocked_seconds": round(float(blocked_s), 6),
        }
        if push_s:
            streaming["overlap_ratio"] = round(
                min(max(1.0 - float(blocked_s) / float(push_s), 0.0),
                    1.0), 3)
        report["streaming"] = streaming
    return report


def summarize_online(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> online-plane report (docs/ONLINE.md): freshness —
    the newest-applied-update age gauge plus per-entry apply-age
    percentiles, deltas applied vs degraded-to-full-refresh (by reason);
    the dense hot-swap gate — attempts / accepted / refusals by reason
    and the last shadow divergence; and the continuous trainer — steps,
    examples, exports, push failures, last loss.  Every series here is
    declared in ``lightctr_tpu.online.ONLINE_SERIES`` (lint-enforced)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})

    def _by_label(prefix, label):
        out = {}
        p = prefix + "{" + label + '="'
        for name, val in counters.items():
            if name.startswith(p):
                out[name[len(p):].rstrip('"}')] = val
        return out

    report: dict = {}
    full = _by_label("serve_freshness_full_refresh_total", "reason")
    freshness = {
        "polls": counters.get("serve_freshness_polls_total", 0),
        "deltas_applied": counters.get(
            "serve_freshness_deltas_applied_total", 0),
        "rows_dropped": counters.get(
            "serve_freshness_rows_dropped_total", 0),
        "full_refreshes": {"total": sum(full.values()), "by_reason": full},
    }
    if "serve_freshness_age_seconds" in gauges:
        freshness["age_s"] = round(gauges["serve_freshness_age_seconds"], 6)
    if "serve_freshness_apply_age_seconds" in hists:
        freshness["apply_age"] = _hist_summary(
            hists["serve_freshness_apply_age_seconds"])
    # gate on real activity (full_refreshes is a dict and always truthy):
    # a snapshot with no freshness series must omit the section, like
    # the swap/trainer sections do
    if (freshness["polls"] or freshness["deltas_applied"]
            or freshness["rows_dropped"]
            or freshness["full_refreshes"]["total"]
            or "age_s" in freshness or "apply_age" in freshness):
        report["freshness"] = freshness
    refused = _by_label("online_swap_refused_total", "reason")
    attempts = counters.get("online_swap_attempts_total", 0)
    if attempts:
        swap = {
            "attempts": attempts,
            "accepted": counters.get("online_swap_accepted_total", 0),
            "refused": {"total": sum(refused.values()),
                        "by_reason": refused},
        }
        if "online_swap_shadow_diff" in gauges:
            swap["last_shadow_diff"] = gauges["online_swap_shadow_diff"]
        report["swap"] = swap
    steps = counters.get("online_steps_total", 0)
    if steps:
        trainer = {
            "steps": steps,
            "examples": counters.get("online_examples_total", 0),
            "exports": counters.get("online_exports_total", 0),
            "push_failures": counters.get(
                "online_push_failures_total", 0),
        }
        if "online_loss" in gauges:
            trainer["last_loss"] = gauges["online_loss"]
        if "online_export_seconds" in hists:
            trainer["export_time"] = _hist_summary(
                hists["online_export_seconds"])
        report["trainer"] = trainer
    return report


def summarize_quality(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> model-quality report (docs/OBSERVABILITY.md
    "Model-quality plane"): per-component streaming calibration ratio,
    sketch-AUC, logloss EWMA vs frozen baseline, examples/windows
    sketched, per-field drift scores, and feature-coverage totals.
    Every series here is declared in
    ``lightctr_tpu.obs.quality.QUALITY_SERIES`` (lint-enforced)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})

    def _labels(name, prefix):
        return dict(
            part.split("=", 1)
            for part in name[len(prefix) + 1:-1].replace('"', "").split(",")
        )

    comps: dict = {}

    def _comp(labels):
        return comps.setdefault(labels.get("component", "?"), {})

    for prefix, key in (("quality_examples_total", "examples"),
                        ("quality_windows_total", "windows")):
        for name, val in counters.items():
            if name.startswith(prefix + "{"):
                _comp(_labels(name, prefix))[key] = int(val)
    for prefix, key in (("quality_calibration_ratio", "calibration_ratio"),
                        ("quality_auc", "auc"),
                        ("quality_logloss_ewma", "logloss_ewma"),
                        ("quality_logloss_baseline", "logloss_baseline")):
        for name, val in gauges.items():
            if name.startswith(prefix + "{"):
                _comp(_labels(name, prefix))[key] = round(float(val), 6)
    prefix = "quality_drift_score"
    for name, val in gauges.items():
        if name.startswith(prefix + "{"):
            labels = _labels(name, prefix)
            _comp(labels).setdefault("drift", {})[
                labels.get("field", "?")] = round(float(val), 6)
    prefix = "quality_coverage_total"
    for name, val in counters.items():
        if name.startswith(prefix + "{"):
            labels = _labels(name, prefix)
            _comp(labels).setdefault("coverage", {})[
                labels.get("field", "?")] = int(val)
    report: dict = {"components": {k: comps[k] for k in sorted(comps)}}
    worst = None
    for comp, entry in comps.items():
        for field, score in entry.get("drift", {}).items():
            if worst is None or score > worst["score"]:
                worst = {"component": comp, "field": field, "score": score}
    if worst is not None:
        report["worst_drift"] = worst
    return report


def summarize_resources(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> resource/saturation report (docs/OBSERVABILITY.md
    "Resource & saturation plane"): per-fn jit compile counts and live
    cache-entry ladders, per-queue depth/capacity/fill with queued-wait
    percentiles, and the memory byte/budget table.  Every series here is
    declared in ``lightctr_tpu.obs.resources.RESOURCE_SERIES``
    (lint-enforced)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})

    def _labels(name, prefix):
        return dict(
            part.split("=", 1)
            for part in name[len(prefix) + 1:-1].replace('"', "").split(",")
        )

    report: dict = {}
    compiles: dict = {}
    for name, val in counters.items():
        if name.startswith("resource_jit_compiles_total{"):
            fn = _labels(name, "resource_jit_compiles_total").get("fn", "?")
            compiles.setdefault(fn, {})["compiles"] = int(val)
    for name, val in gauges.items():
        if name.startswith("resource_jit_cache_entries{"):
            fn = _labels(name, "resource_jit_cache_entries").get("fn", "?")
            compiles.setdefault(fn, {})["cache_entries"] = int(val)
    jit = {"fns": {k: compiles[k] for k in sorted(compiles)}}
    if "resource_backend_compiles_total" in counters:
        jit["backend_compiles"] = int(
            counters["resource_backend_compiles_total"])
    if "resource_compile_seconds" in hists:
        jit["compile_time"] = _hist_summary(hists["resource_compile_seconds"])
    if jit["fns"] or len(jit) > 1:
        report["jit"] = jit
    queues: dict = {}

    def _queue(labels):
        return queues.setdefault(labels.get("queue", "?"), {})

    for prefix, key in (("resource_queue_depth", "depth"),
                        ("resource_queue_capacity", "capacity")):
        for name, val in gauges.items():
            if name.startswith(prefix + "{"):
                _queue(_labels(name, prefix))[key] = int(val)
    for prefix, key in (("resource_queue_enqueued_total", "enqueued"),
                        ("resource_queue_dropped_total", "dropped")):
        for name, val in counters.items():
            if name.startswith(prefix + "{"):
                _queue(_labels(name, prefix))[key] = int(val)
    prefix = "resource_queue_wait_seconds"
    for name, hist in hists.items():
        if name.startswith(prefix + "{"):
            _queue(_labels(name, prefix))["wait"] = _hist_summary(hist)
    worst = None
    for qname, entry in queues.items():
        cap = entry.get("capacity", 0)
        if cap:
            entry["fill"] = round(entry.get("depth", 0) / cap, 4)
            if worst is None or entry["fill"] > worst["fill"]:
                worst = {"queue": qname, "fill": entry["fill"]}
    if queues:
        report["queues"] = {k: queues[k] for k in sorted(queues)}
    if worst is not None:
        report["fullest_queue"] = worst
    memory: dict = {}
    for prefix, key in (("resource_memory_bytes", "bytes"),
                        ("resource_memory_budget_bytes", "budget_bytes")):
        for name, val in gauges.items():
            if name.startswith(prefix + "{"):
                kind = _labels(name, prefix).get("kind", "?")
                memory.setdefault(kind, {})[key] = int(val)
    for kind, entry in memory.items():
        if entry.get("budget_bytes"):
            entry["fraction"] = round(
                entry.get("bytes", 0) / entry["budget_bytes"], 4)
    if memory:
        report["memory"] = {k: memory[k] for k in sorted(memory)}
    return report


def summarize_ingest(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> compiled-data-plane report (docs/INGEST.md): the
    shard cache (compiles vs hits vs torn-cache recoveries, rows/bytes
    written, blocks replayed) and the prefetch pipeline (batches
    delivered, gets served without blocking, the ``ingest_overlap_ratio``
    honesty gauge, consumer-wait percentiles, and the prefetch queue's
    depth/capacity/fill from its ``resource_queue_*`` face).  Every
    series here is declared in
    ``lightctr_tpu.data.ingest.INGEST_SERIES`` (lint-enforced)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})

    report: dict = {}
    cache = {
        "compiles": int(counters.get("ingest_shard_compiles_total", 0)),
        "cache_hits": int(
            counters.get("ingest_shard_cache_hits_total", 0)),
        "recoveries": int(
            counters.get("ingest_shard_recoveries_total", 0)),
        "rows_written": int(counters.get("ingest_shard_rows_total", 0)),
        "bytes_written": int(counters.get("ingest_shard_bytes_total", 0)),
        "blocks_replayed": int(
            counters.get("ingest_replay_blocks_total", 0)),
    }
    if any(cache.values()):
        report["shard_cache"] = cache
    batches = int(counters.get("ingest_prefetch_batches_total", 0))
    if batches or "ingest_overlap_ratio" in gauges:
        prefetch = {
            "batches": batches,
            "ready": int(counters.get("ingest_prefetch_ready_total", 0)),
        }
        if "ingest_overlap_ratio" in gauges:
            prefetch["overlap_ratio"] = round(
                float(gauges["ingest_overlap_ratio"]), 4)
        if "ingest_wait_seconds" in hists:
            prefetch["wait"] = _hist_summary(hists["ingest_wait_seconds"])
        prefix = 'resource_queue_depth{queue="ingest_prefetch"}'
        if prefix in gauges:
            queue = {"depth": int(gauges[prefix])}
            cap = gauges.get(
                'resource_queue_capacity{queue="ingest_prefetch"}')
            if cap:
                queue["capacity"] = int(cap)
                queue["fill"] = round(queue["depth"] / int(cap), 4)
            prefetch["queue"] = queue
        report["prefetch"] = prefetch
    return report


def summarize_seq(doc, held_experts: int = 0) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> the sequence tower's report: positions, targets and
    packed documents the softmax loss counted (``trainer_seq_*_total``,
    off the step's health vector) and, per routed expert layer, the
    assignments the router made, the share of them that went to experts
    this chip holds (``held_share``), and the load of the busiest held
    expert against the mean (``max_over_mean``:
    ``trainer_moe_expert_tokens_max`` is a step's maximum summed over
    steps, the mean is the held assignments over ``held_experts``, which
    the caller states — the counters do not carry it)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    report: dict = {}
    seq = {what: int(counters[f"trainer_seq_{what}_total"])
           for what in ("tokens", "targets", "documents")
           if f"trainer_seq_{what}_total" in counters}
    if seq:
        if seq.get("documents"):
            seq["tokens_per_document"] = round(
                seq.get("tokens", 0) / seq["documents"], 1)
        report["sequences"] = seq
    layers: dict = {}
    for what, name in (("assignments", "trainer_moe_assignments_total"),
                       ("held", "trainer_moe_held_assignments_total"),
                       ("max_sum", "trainer_moe_expert_tokens_max")):
        for key, val in counters.items():
            if key.startswith(name + "{"):
                layer = key[len(name) + 1:-1].replace('"', "").split("=", 1)[1]
                layers.setdefault(layer, {})[what] = int(val)
    for entry in layers.values():
        if entry.get("assignments"):
            entry["held_share"] = round(
                entry.get("held", 0) / entry["assignments"], 4)
        most = entry.pop("max_sum", None)
        if most is not None and held_experts and entry.get("held"):
            entry["max_over_mean"] = round(
                most / (entry["held"] / held_experts), 3)
    if layers:
        report["moe_layers"] = dict(sorted(layers.items()))
    return report


def summarize_device(doc) -> dict:
    """Registry snapshot (or a stats() dump carrying one under
    ``telemetry``) -> device/compiled-program report
    (docs/OBSERVABILITY.md "Device plane"): per-program FLOPs / bytes
    accessed / arithmetic intensity / roofline utilization with the
    compiled memory breakdown and step-time percentiles, the live-buffer
    census table vs budgets, donation check/miss counters, and profiler
    capture/refusal totals.  Every series here is declared in
    ``lightctr_tpu.obs.device.DEVICE_SERIES`` (lint-enforced)."""
    snap = doc.get("telemetry", doc) if isinstance(doc, dict) else doc
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})

    def _labels(name, prefix):
        return dict(
            part.split("=", 1)
            for part in name[len(prefix) + 1:-1].replace('"', "").split(",")
        )

    report: dict = {}
    programs: dict = {}
    for prefix, key in (("device_program_flops", "flops"),
                        ("device_program_bytes_accessed", "bytes_accessed"),
                        ("device_program_intensity", "intensity"),
                        ("device_program_utilization", "utilization")):
        for name, val in gauges.items():
            if name.startswith(prefix + "{"):
                prog = _labels(name, prefix).get("program", "?")
                programs.setdefault(prog, {})[key] = round(float(val), 6)
    prefix = "device_program_memory_bytes"
    for name, val in gauges.items():
        if name.startswith(prefix + "{"):
            labels = _labels(name, prefix)
            programs.setdefault(labels.get("program", "?"), {}).setdefault(
                "memory", {})[labels.get("kind", "?")] = int(val)
    prefix = "device_program_time_seconds"
    for name, hist in hists.items():
        if name.startswith(prefix + "{"):
            prog = _labels(name, prefix).get("program", "?")
            programs.setdefault(prog, {})["time"] = _hist_summary(hist)
    if programs:
        report["programs"] = {k: programs[k] for k in sorted(programs)}
        worst = None
        for prog, entry in programs.items():
            util = entry.get("utilization")
            if util is not None and (worst is None
                                     or util < worst["utilization"]):
                worst = {"program": prog, "utilization": util}
        if worst is not None:
            report["lowest_utilization"] = worst
    live: dict = {}
    for prefix, key in (("device_live_buffer_bytes", "bytes"),
                        ("device_live_buffer_count", "buffers"),
                        ("device_live_budget_bytes", "budget_bytes")):
        for name, val in gauges.items():
            if name.startswith(prefix + "{"):
                tag = _labels(name, prefix).get("tag", "?")
                live.setdefault(tag, {})[key] = int(val)
    for tag, entry in live.items():
        if entry.get("budget_bytes"):
            entry["fraction"] = round(
                entry.get("bytes", 0) / entry["budget_bytes"], 4)
    if live:
        report["live"] = {k: live[k] for k in sorted(live)}
    donation: dict = {}
    for prefix, key in (("device_donation_checks_total", "checks"),
                        ("device_donation_miss_total", "misses")):
        for name, val in counters.items():
            if name.startswith(prefix + "{"):
                prog = _labels(name, prefix).get("program", "?")
                donation.setdefault(prog, {})[key] = int(val)
    if donation:
        report["donation"] = {k: donation[k] for k in sorted(donation)}
    profile: dict = {}
    if "device_profile_captures_total" in counters:
        profile["captures"] = int(counters["device_profile_captures_total"])
    prefix = "device_profile_refused_total"
    for name, val in counters.items():
        if name.startswith(prefix + "{"):
            profile.setdefault("refused", {})[
                _labels(name, prefix).get("reason", "?")] = int(val)
    if profile:
        report["profile"] = profile
    return report


def summarize_cluster(doc) -> dict:
    """Cluster rollup dump -> straggler/rollup report.  Accepts the
    :meth:`~lightctr_tpu.obs.cluster.ClusterRollup.members` dict, a bare
    ``{member: stats-or-snapshot}`` map, or the list
    ``ShardedPSClient.stats()`` returns (down shards become
    ``scrape_down`` members — the same never-vanish rule)."""
    from lightctr_tpu.obs.cluster import attribute_stragglers

    members: dict = {}

    def _entry(name, st):
        if isinstance(st, dict) and (st.get("down") or st.get("scrape_down")):
            return {"member": name, "scrape_down": True,
                    "error": st.get("error"), "snapshot": {}}
        if isinstance(st, dict) and "snapshot" in st:
            e = dict(st)
            e.setdefault("member", name)
            e.setdefault("scrape_down", False)
            return e
        snap = {}
        if isinstance(st, dict):
            snap = st.get("telemetry", st if "counters" in st
                          or "histograms" in st or "gauges" in st else {})
        return {"member": name, "scrape_down": False,
                "snapshot": snap or {}}

    if isinstance(doc, list):
        for i, st in enumerate(doc):
            name = (str(st.get("shard", i)) if isinstance(st, dict)
                    else str(i))
            members[f"shard_{name}"] = _entry(f"shard_{name}", st)
    elif isinstance(doc, dict):
        for name, st in doc.items():
            members[str(name)] = _entry(str(name), st)
    report = attribute_stragglers(members)
    report["members_total"] = len(members)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("jsonl", nargs="?", help="event-log path (JSONL)")
    ap.add_argument("--out", help="write the report JSON here too")
    ap.add_argument("--prom", metavar="SNAPSHOT_JSON",
                    help="render a registry-snapshot JSON as Prometheus "
                         "text instead of summarizing an event log")
    ap.add_argument("--health", metavar="PATH",
                    help="summarize health events (verdict timeline + "
                         "final states) from a JSONL file or a directory "
                         "of per-process JSONL logs")
    ap.add_argument("--serve", metavar="STATS_JSON",
                    help="summarize serve-side histograms and cache "
                         "counters from a PredictionServer stats() dump "
                         "or a bare registry snapshot")
    ap.add_argument("--store", metavar="STATS_JSON",
                    help="summarize store occupancy (flat AND tiered) "
                         "from a PS stats() dump — one shard's dict or a "
                         "ShardedPSClient.stats() list")
    ap.add_argument("--kernels", metavar="SNAPSHOT_JSON",
                    help="summarize the registered kernels' dispatch "
                         "counts (trainer_kernel_path_total{phase,impl}) "
                         "from a registry snapshot or stats() dump")
    ap.add_argument("--online", metavar="SNAPSHOT_JSON",
                    help="summarize the online learning plane (freshness "
                         "age + deltas applied vs full refreshes, swap "
                         "attempts/refusals, continuous-trainer counters) "
                         "from a registry snapshot or stats() dump")
    ap.add_argument("--exchange", metavar="SNAPSHOT_JSON",
                    help="summarize gradient-exchange decisions and bytes "
                         "(trainer_exchange_*/trainer_hier_* series, the "
                         "hierarchical per-hop local/wire split included) "
                         "from a registry snapshot or stats() dump")
    ap.add_argument("--cluster", metavar="MEMBERS_JSON",
                    help="cluster straggler report from a ClusterRollup "
                         "members() dump, {member: stats} map, or "
                         "ShardedPSClient.stats() list")
    ap.add_argument("--quality", metavar="SNAPSHOT_JSON",
                    help="summarize the model-quality plane (calibration "
                         "ratio, sketch-AUC, logloss EWMA vs baseline, "
                         "drift scores, feature coverage) from a registry "
                         "snapshot or stats() dump")
    ap.add_argument("--resources", metavar="SNAPSHOT_JSON",
                    help="summarize the resource/saturation plane (jit "
                         "compiles + cache ladders, queue depth/fill with "
                         "wait percentiles, memory bytes vs budgets) from "
                         "a registry snapshot or stats() dump")
    ap.add_argument("--device", metavar="SNAPSHOT_JSON",
                    help="summarize the device/compiled-program plane "
                         "(per-program FLOPs/bytes/intensity/roofline "
                         "utilization + memory breakdown, live-buffer "
                         "census vs budgets, donation misses, profiler "
                         "captures) from a registry snapshot or stats() "
                         "dump")
    ap.add_argument("--ingest", metavar="SNAPSHOT_JSON",
                    help="summarize the compiled data plane (shard-cache "
                         "compiles/hits/recoveries + rows/bytes, blocks "
                         "replayed, prefetch batches/ready with the "
                         "overlap-ratio honesty gauge, consumer-wait "
                         "percentiles, prefetch queue fill) from a "
                         "registry snapshot or stats() dump")
    ap.add_argument("--seq", metavar="SNAPSHOT_JSON",
                    help="summarize the sequence tower (positions, targets "
                         "and documents the softmax loss counted; per routed "
                         "layer the held share of the router's assignments "
                         "and, with --held-experts, the busiest held "
                         "expert's load over the mean) from a registry "
                         "snapshot or stats() dump")
    ap.add_argument("--held-experts", type=int, default=0,
                    help="experts this chip holds a layer (for --seq's "
                         "max_over_mean)")
    args = ap.parse_args(argv)

    if args.seq:
        with open(args.seq) as f:
            doc = json.load(f)
        report = summarize_seq(doc, args.held_experts)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.prom:
        with open(args.prom) as f:
            snap = json.load(f)
        sys.stdout.write(render_prometheus(snap, prefix="lightctr_"))
        return 0
    if args.health:
        records = []
        for p in _expand_jsonl(args.health):
            records.extend(read_jsonl(p))
        report = summarize_health(records)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.serve:
        with open(args.serve) as f:
            doc = json.load(f)
        report = summarize_serve(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.store:
        with open(args.store) as f:
            doc = json.load(f)
        report = summarize_store(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.online:
        with open(args.online) as f:
            doc = json.load(f)
        report = summarize_online(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.exchange:
        with open(args.exchange) as f:
            doc = json.load(f)
        report = summarize_exchange(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.kernels:
        with open(args.kernels) as f:
            doc = json.load(f)
        report = summarize_kernels(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.cluster:
        with open(args.cluster) as f:
            doc = json.load(f)
        report = summarize_cluster(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.quality:
        with open(args.quality) as f:
            doc = json.load(f)
        report = summarize_quality(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.resources:
        with open(args.resources) as f:
            doc = json.load(f)
        report = summarize_resources(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.device:
        with open(args.device) as f:
            doc = json.load(f)
        report = summarize_device(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if args.ingest:
        with open(args.ingest) as f:
            doc = json.load(f)
        report = summarize_ingest(doc)
        print(json.dumps(report, indent=1))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return 0
    if not args.jsonl:
        ap.error("give an event-log path, --prom SNAPSHOT_JSON, "
                 "--health PATH, --serve STATS_JSON, --store STATS_JSON, "
                 "--kernels SNAPSHOT_JSON, --exchange SNAPSHOT_JSON, "
                 "--cluster MEMBERS_JSON, --quality SNAPSHOT_JSON, "
                 "--resources SNAPSHOT_JSON, --device SNAPSHOT_JSON, "
                 "--ingest SNAPSHOT_JSON, or --online SNAPSHOT_JSON")

    report = summarize(read_jsonl(args.jsonl))
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
