"""Process-local metric registry with GCS push.

Reference: src/ray/stats/ (OpenCensus registry in every process) +
python/ray/_private/metrics_agent.py (per-node agent re-exposing
Prometheus). Simplification, same shape: every process registers metrics
locally and pushes snapshots to the GCS on a short cadence; the dashboard
exposes the aggregate as Prometheus text.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Any, Dict, List, Optional, Tuple

_lock = threading.Lock()
_registry: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], "_Metric"] = {}
_generation = 0      # how many registries this process has started
_pusher: Optional[threading.Thread] = None
_push_stop = threading.Event()

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000]


class _Metric:
    def __init__(self, name: str, kind: str, description: str,
                 tags: Dict[str, str],
                 boundaries: Optional[List[float]] = None):
        self.name = name
        self.kind = kind  # counter | gauge | histogram
        self.description = description
        self.tags = dict(tags)
        self.value = 0.0
        self.boundaries = boundaries or []
        self.bucket_counts = [0] * (len(self.boundaries) + 1)
        self.sum = 0.0
        self.count = 0

    def snapshot(self) -> Dict[str, Any]:
        out = {"name": self.name, "kind": self.kind,
               "description": self.description, "tags": self.tags,
               "value": self.value}
        if self.kind == "histogram":
            out.update({"boundaries": self.boundaries,
                        "bucket_counts": self.bucket_counts,
                        "sum": self.sum, "count": self.count})
        return out


def register(name: str, kind: str, description: str,
             tags: Dict[str, str],
             boundaries: Optional[List[float]] = None) -> _Metric:
    key = (name, tuple(sorted(tags.items())))
    with _lock:
        metric = _registry.get(key)
        if metric is None:
            metric = _registry[key] = _Metric(name, kind, description,
                                              tags, boundaries)
        return metric


def registry_generation() -> int:
    """Changes whenever `reset_registry` drops the series: a `_Metric`
    a caller kept from `register` is the registry's own only while this
    reads what it read then."""
    return _generation


def record(metric: _Metric, value: float, kind: str, n: int = 1) -> None:
    """One record; a histogram takes `n` equal observations as one."""
    with _lock:
        if kind == "counter":
            metric.value += value
        elif kind == "gauge":
            metric.value = value
        else:
            metric.sum += value * n
            metric.count += n
            metric.bucket_counts[bisect_left(metric.boundaries,
                                             value)] += n


def snapshots() -> List[Dict[str, Any]]:
    with _lock:
        return [m.snapshot() for m in _registry.values()]


def reset_registry() -> None:
    """Drop every registered series (TEST ISOLATION, not production):
    the process-local registry is module state, so counters recorded by
    one test module would otherwise leak into the next module's
    snapshots()/prometheus_text() assertions. Metric objects held by
    callers (EngineMetrics instruments, fleet gauge caches) stay valid
    — register() lazily re-creates a series on the next record."""
    global _generation
    with _lock:
        _registry.clear()
        _generation += 1


# -- Prometheus text exposition ---------------------------------------------
#
# The ONE renderer for metric snapshots -> exposition format, shared by
# the dashboard head's /metrics route (GCS-aggregated rows) and
# util.metrics.prometheus_text() (this process's registry). Keeping it
# next to the registry means the snapshot dict shape and its renderer
# can never drift apart.

def escape_label(value: str) -> str:
    """Prometheus exposition-format label escaping (backslash, quote,
    newline) — unescaped user tag values would break the whole scrape."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    """HELP-line escaping: backslash and newline only (the format
    leaves quotes alone there)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def prometheus_text(rows: Optional[List[Dict[str, Any]]] = None,
                    prefix: str = "ray_tpu_") -> str:
    """Render metric snapshot rows (`snapshots()` by default) as
    Prometheus text exposition: one `# HELP` / `# TYPE` header per
    metric with every series of that metric grouped under it (the
    format REQUIRES samples of one metric to be contiguous), sorted
    label rendering, and cumulative histogram `_bucket{le=...}` lines
    ending in the implicit `+Inf` bucket plus `_sum` / `_count`.
    Metric names are mangled `<prefix> + name.replace('.', '_')` —
    `util.metrics` dots become Prometheus underscores."""
    if rows is None:
        rows = snapshots()
    groups: Dict[str, List[Dict[str, Any]]] = {}
    for m in rows:
        name = prefix + m["name"].replace(".", "_")
        groups.setdefault(name, []).append(m)
    lines: List[str] = []
    for name, ms in groups.items():
        first = ms[0]
        if first.get("description"):
            lines.append(
                f"# HELP {name} {_escape_help(first['description'])}")
        kind = {"counter": "counter", "gauge": "gauge",
                "histogram": "histogram"}[first["kind"]]
        lines.append(f"# TYPE {name} {kind}")
        for m in ms:
            tag_str = ",".join(f'{k}="{escape_label(v)}"'
                               for k, v in sorted(m["tags"].items()))
            label = f"{{{tag_str}}}" if tag_str else ""
            if m["kind"] == "histogram":
                cumulative = 0
                bounds = m.get("boundaries", [])
                for i, c in enumerate(m.get("bucket_counts", [])):
                    cumulative += c
                    le = bounds[i] if i < len(bounds) else "+Inf"
                    extra = f'le="{le}"'
                    tags = (f"{{{tag_str},{extra}}}" if tag_str
                            else f"{{{extra}}}")
                    lines.append(f"{name}_bucket{tags} {cumulative}")
                lines.append(f"{name}_sum{label} {m.get('sum', 0)}")
                lines.append(f"{name}_count{label} {m.get('count', 0)}")
            else:
                lines.append(f"{name}{label} {m['value']}")
    return "\n".join(lines) + "\n"


def _push_loop(interval_s: float) -> None:
    from ray_tpu._private.worker import global_worker_or_none

    while not _push_stop.wait(interval_s):
        worker = global_worker_or_none()
        if worker is None:
            continue
        snaps = snapshots()
        if not snaps:
            continue
        try:
            worker.gcs_call("report_metrics", {
                "worker_id": worker.core.worker_id.binary(),
                "metrics": snaps})
        except Exception:
            pass


def ensure_pusher(interval_s: float = 2.0) -> None:
    global _pusher
    with _lock:
        if _pusher is None or not _pusher.is_alive():
            _push_stop.clear()
            _pusher = threading.Thread(
                target=_push_loop, args=(interval_s,), daemon=True,
                name="metrics-pusher")
            _pusher.start()


def flush_now() -> None:
    """Synchronous push (tests / shutdown)."""
    from ray_tpu._private.worker import global_worker_or_none

    worker = global_worker_or_none()
    if worker is None:
        return
    snaps = snapshots()
    if snaps:
        worker.gcs_call("report_metrics", {
            "worker_id": worker.core.worker_id.binary(),
            "metrics": snaps})
