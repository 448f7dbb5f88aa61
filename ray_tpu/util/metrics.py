"""User-facing metrics API: Counter / Gauge / Histogram.

Reference: python/ray/util/metrics.py (same three classes, same
tag_keys/default-tags shape) over the native stats registry
(src/ray/stats/). Metrics recorded in any worker flow to the GCS and are
exposed as Prometheus text by the dashboard (/metrics).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import metrics as _impl


def snapshots() -> List[Dict[str, Any]]:
    """Snapshot every metric series registered IN THIS PROCESS — rows
    of ``{name, kind, description, tags, value}`` (histograms add
    ``boundaries/bucket_counts/sum/count``). This is the local view;
    the dashboard's /metrics aggregates the same rows cluster-wide via
    the GCS pusher."""
    return _impl.snapshots()


def prometheus_text(rows: Optional[List[Dict[str, Any]]] = None,
                    prefix: str = "ray_tpu_") -> str:
    """Prometheus text exposition of metric snapshot rows (this
    process's registry by default) — scrape-ready: HELP/TYPE headers,
    escaped sorted labels, cumulative histogram buckets. The engine and
    fleet gauges (`llm.engine.*` / `llm.fleet.*`) come out as
    `ray_tpu_llm_engine_*` / `ray_tpu_llm_fleet_*` series."""
    return _impl.prometheus_text(rows, prefix=prefix)


def reset_registry() -> None:
    """TEST HELPER: clear this process's metric registry so series
    recorded by one test module cannot leak ordering or values into
    another's `snapshots()` / `prometheus_text()` assertions. Existing
    Counter/Gauge/Histogram objects keep working — the backing series
    is lazily re-registered on their next record."""
    _impl.reset_registry()


class _Base:
    _kind = ""
    _boundaries: Optional[List[float]] = None      # a Histogram's

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Tuple[str, ...]] = None):
        if not name:
            raise ValueError("metric name is required")
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        # the series of the default tags, resolved once for the
        # registry it was resolved in (`reset_registry` starts another)
        self._series: Optional[Tuple[int, Any]] = None
        _impl.ensure_pusher()

    def set_default_tags(self, tags: Dict[str, str]):
        bad = set(tags) - set(self._tag_keys)
        if bad:
            raise ValueError(f"tags {sorted(bad)} not in tag_keys")
        self._default_tags = dict(tags)
        self._series = None
        return self

    def _resolve(self, tags: Optional[Dict[str, str]]):
        """The registry's series for `tags` over the defaults. A call
        without tags of its own (every hot-path one) takes the series
        kept from the call before: no dict, no key, no registry lock."""
        if tags:
            return _impl.register(self._name, self._kind,
                                  self._description, self._merged(tags),
                                  self._boundaries)
        kept = self._series
        if kept is None or kept[0] != _impl.registry_generation():
            kept = self._series = (
                _impl.registry_generation(),
                _impl.register(self._name, self._kind, self._description,
                               dict(self._default_tags), self._boundaries))
        return kept[1]

    def _merged(self, tags: Optional[Dict[str, str]]) -> Dict[str, str]:
        merged = dict(self._default_tags)
        if tags:
            bad = set(tags) - set(self._tag_keys)
            if bad:
                raise ValueError(f"tags {sorted(bad)} not in tag_keys")
            merged.update(tags)
        return merged

    @property
    def info(self) -> Dict[str, object]:
        return {"name": self._name, "description": self._description,
                "tag_keys": self._tag_keys,
                "default_tags": dict(self._default_tags)}


class Counter(_Base):
    _kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        if value <= 0:
            raise ValueError("Counter.inc value must be positive")
        _impl.record(self._resolve(tags), value, "counter")


class Gauge(_Base):
    _kind = "gauge"

    def set(self, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
        _impl.record(self._resolve(tags), value, "gauge")


class Histogram(_Base):
    _kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[List[float]] = None,
                 tag_keys: Optional[Tuple[str, ...]] = None):
        super().__init__(name, description, tag_keys)
        self._boundaries = list(
            boundaries or _impl.DEFAULT_HISTOGRAM_BOUNDARIES)

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None,
                n: int = 1) -> None:
        """One observation, or `n` equal ones at the cost of one."""
        _impl.record(self._resolve(tags), value, "histogram", n)
