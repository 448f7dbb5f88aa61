"""`ray status`-style report over the serving state API.

Renders, from one snapshot:

- fleet topology: replicas per fleet, router, tp degree, draining
  flags, autoscaler presence, replica health census (SUSPECT /
  failed / recovered counters when the fault-tolerance plane has
  anything to say);
- one line per engine with occupancy / queue / KV-pool bars and its
  fleet health state (SUSPECT and worse shown as a flag);
- SLO percentiles (TTFT/TPOT p50/p95) with trend arrows derived from
  the metrics-history ring;
- the top-N longest-running in-flight requests with their current
  phase (queued / prefilling / decoding / swapped).

Run against a live dashboard head:

    python tools/ray_tpu_status.py --addr http://127.0.0.1:8265

or in-process (no HTTP): import `collect` / `format_status` and call
them beside a running engine/fleet — which is also how the test drives
a full report off a live 2-replica CPU dry-run fleet. `--json` dumps
the raw collected state for scripting.
"""
from __future__ import annotations

import argparse
import json
from typing import Any, Dict, List, Optional

ARROWS = {1: "^", -1: "v", 0: "-"}
SLO_KEYS = ("ttft_s_p50", "ttft_s_p95", "tpot_s_p50", "tpot_s_p95")


def collect(addr: Optional[str] = None) -> Dict[str, Any]:
    """One coherent snapshot of the serving plane: engines, in-flight
    requests, KV pools, fleet summary, metrics history. From the
    dashboard head's /api/v0 endpoints when ``addr`` is given, else
    from this process's own registrations (a fresh history sample is
    forced so the report is never empty-handed)."""
    if addr is not None:
        import urllib.request

        def get(path):
            with urllib.request.urlopen(addr.rstrip("/") + path,
                                        timeout=10) as r:
                return json.load(r)

        return {"engines": get("/api/v0/state/engines"),
                "requests": get("/api/v0/state/requests"),
                "kv_pools": get("/api/v0/state/kv_pools"),
                "summary": get("/api/v0/state/summary"),
                "history": get("/api/v0/metrics_history")}

    from ray_tpu.util import metrics_history as mh
    from ray_tpu.util.state import serving

    mh.sample_now(force=True)
    return {"engines": serving.list_engines(),
            "requests": serving.list_requests(),
            "kv_pools": serving.list_kv_pools(),
            "summary": serving.summarize_fleet(),
            "history": mh.global_history().snapshot()}


def _bar(frac: float, width: int = 20) -> str:
    frac = max(0.0, min(1.0, float(frac)))
    fill = int(round(frac * width))
    return "[" + "#" * fill + "-" * (width - fill) + "]"


def _phases_line(counts: Dict[str, int]) -> str:
    # handoff/recovering are disagg/fault-plane phases: shown only
    # when non-zero so the common colocated report stays four terms.
    order = ("queued", "prefilling", "decoding", "swapped",
             "handoff", "recovering")
    parts = [f"{counts.get(p, 0)} {p}" for p in order
             if p not in ("handoff", "recovering") or counts.get(p, 0)]
    return " / ".join(parts)


def _trends(history: Dict[str, Any]) -> Dict[str, int]:
    """Per-SLO-key trend arrow direction from a history SNAPSHOT (the
    JSON shape both the endpoint and `MetricsHistory.snapshot`
    return)."""
    from ray_tpu.util.metrics_history import trend_of_points

    samples = history.get("samples", [])
    return {k: trend_of_points([s[k] for s in samples if k in s])
            for k in SLO_KEYS}


def format_status(data: Dict[str, Any], top: int = 5) -> str:
    """The report text. Pure formatting over `collect()`'s dict — no
    live state is touched, so tests can feed synthetic snapshots."""
    engines: List[Dict[str, Any]] = data["engines"]
    requests: List[Dict[str, Any]] = data["requests"]
    pools = {p["engine_id"]: p for p in data["kv_pools"]}
    summary = data["summary"]
    lines: List[str] = []

    lines.append("======== Fleet ========")
    for fb in summary["fleets"]:
        drain = (f", {fb['replicas_draining']} draining"
                 if fb["replicas_draining"] else "")
        auto = " autoscaling" if fb.get("autoscaling") else ""
        if fb.get("disaggregated"):
            # Class census + handoff counter: the disagg fleet's
            # topology at a glance (prefill/decode split).
            auto += (f" disagg[{fb.get('replicas_prefill', 0)}P/"
                     f"{fb.get('replicas_decode', 0)}D "
                     f"{fb.get('handoffs', 0)} handoffs]")
        health = fb.get("health", {})
        suspect = (f", {health['SUSPECT']} suspect"
                   if health.get("SUSPECT") else "")
        lines.append(
            f"fleet {fb['fleet_id']}: {fb['replicas']} replicas "
            f"({fb['replicas_running']} running{drain}{suspect}) "
            f"router={fb['router']} tp={fb['tp_degree_max']}{auto}")
        lines.append(f"  requests: {_phases_line(fb['requests'])}"
                     f"   shed total: {fb['requests_shed']}")
        if fb.get("replicas_failed") or fb.get("retries") or \
                fb.get("requests_recovering"):
            lines.append(
                f"  faults: {fb.get('replicas_failed', 0)} replica(s) "
                f"failed, {fb.get('requests_recovered', 0)} requests "
                f"recovered ({fb.get('retries', 0)} retries), "
                f"{fb.get('requests_recovering', 0)} recovering now, "
                f"{fb.get('tokens_lost_to_failure', 0)} tokens lost")
    if not summary["fleets"]:
        lines.append("no fleets registered")
    if summary["engines_unattached"]:
        lines.append(f"{summary['engines_unattached']} engine(s) "
                     "outside any fleet")
    lines.append("in-flight: " + _phases_line(summary["requests"]))

    lines.append("")
    lines.append("======== Replicas ========")
    # Acceptance trend is fleet-wide (the history ring samples one
    # proposal-weighted rate across engines); each spec replica's line
    # shows its own instantaneous rate with the shared arrow.
    from ray_tpu.util.metrics_history import trend_of_points
    hist_samples = data.get("history", {}).get("samples", [])
    spec_arrow = ARROWS[trend_of_points(
        [s["spec_acceptance_rate"] for s in hist_samples
         if "spec_acceptance_rate" in s])]
    for e in engines:
        pool = pools.get(e["engine_id"])
        if pool:
            # A quantized pool tags its KV bar with the storage dtype
            # and per-block byte cost (scale slab included) — the
            # concurrency-per-HBM-byte lever at a glance.
            quant = pool.get("quant")
            qtag = (f" {quant} {pool.get('bytes_per_block', 0.0):.0f}B/blk"
                    if quant else "")
            kv = (f" kv {_bar(pool.get('occupancy', 0.0), 10)} "
                  f"{pool.get('blocks_in_use', 0)}/"
                  f"{pool.get('blocks_total', 0)} blk{qtag}")
        else:
            kv = ""
        spec = ""
        if e.get("spec_enabled"):
            spec = (f" spec w{e.get('spec_window', 0)} "
                    f"acc {e.get('spec_acceptance_rate', 0.0) * 100:.0f}%"
                    f" {spec_arrow}")
        health = e.get("health")
        klass = e.get("replica_class")
        flags = "".join(
            [" DRAINING" if e["draining"] else "",
             # RUNNING is the quiet default; anything else (SUSPECT,
             # UNHEALTHY) is worth a loud flag on the replica line.
             f" {health}" if health not in (None, "RUNNING",
                                            "DRAINING") else "",
             # Replica class column (disaggregated fleets): colocated
             # replicas stay untagged so mixed pools read cleanly.
             f" class={klass}" if klass else "",
             f" tp={e['tp_degree']}" if e["tp_degree"] > 1 else ""])
        lines.append(
            f"{e['engine_id']:>16} "
            f"occ {_bar(e['slot_occupancy'], 10)} "
            f"{e['live_slots']}/{e['batch_slots']} "
            f"queue {e['queue_depth']:>3}{kv} "
            f"up {e['uptime_s']:.1f}s steps {e['steps_total']}"
            f"{spec}{flags}")
    if not engines:
        lines.append("no engines registered")

    lines.append("")
    lines.append("======== SLO (recent window) ========")
    arrows = _trends(data.get("history", {}))
    samples = data.get("history", {}).get("samples", [])
    last = samples[-1] if samples else {}
    for key in SLO_KEYS:
        val = last.get(key)
        shown = f"{val * 1e3:8.2f} ms" if val is not None else \
            "     n/a   "
        lines.append(f"{key:>12}: {shown}  {ARROWS[arrows[key]]}")
    lines.append(f"history: {len(samples)} samples retained, "
                 f"{data.get('history', {}).get('compactions', 0)} "
                 "compactions")

    lines.append("")
    lines.append(f"======== Longest-running requests (top {top}) "
                 "========")
    with_age = [r for r in requests if r.get("age_s") is not None]
    with_age.sort(key=lambda r: -r["age_s"])
    for r in with_age[:top]:
        where = f"row {r['row']}" if r.get("row") is not None \
            else "unplaced"
        extra = ""
        if r["status"] == "prefilling" and "prefill_pos" in r:
            extra = (f" prefill {r['prefill_pos']}/"
                     f"{r['prompt_tokens']}")
        lines.append(
            f"req {r['req_id']:>5} @{r['engine_id']:<16} "
            f"{r['status']:<10} age {r['age_s']:7.2f}s "
            f"tokens {r.get('tokens_out', 0)}/"
            f"{r.get('max_new_tokens', '?')} {where}{extra}")
    if not with_age:
        lines.append("no in-flight requests")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--addr", default=None,
                    help="dashboard base URL (e.g. "
                         "http://127.0.0.1:8265); default: this "
                         "process's registrations")
    ap.add_argument("--top", type=int, default=5,
                    help="longest-running requests to show")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw collected snapshot as JSON")
    args = ap.parse_args(argv)
    data = collect(args.addr)
    if args.json:
        print(json.dumps(data, indent=1, default=str))
    else:
        print(format_status(data, top=args.top))


if __name__ == "__main__":
    main()
