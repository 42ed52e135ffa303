"""Hierarchical metrics registries.

Ref: lib/runtime/src/metrics.rs:1-1679 (``MetricsRegistry`` trait :365) and
metrics/prometheus_names.rs — registries keyed by the component hierarchy
(drt → namespace → component → endpoint) with auto-attached labels, exported
in Prometheus text format by the system status server.

Built on ``prometheus_client`` with a thin hierarchy wrapper so metric names
and label sets match the reference's canonical scheme
(``dynamo_component_*`` / ``dynamo_frontend_*``).
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Optional, Tuple

from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram, generate_latest

# Canonical metric name prefixes (ref: prometheus_names.rs).
COMPONENT_PREFIX = "dynamo_component_"
FRONTEND_PREFIX = "dynamo_frontend_"


class MetricsRegistry:
    """A node in the metrics hierarchy. Children inherit labels."""

    def __init__(
        self,
        registry: Optional[CollectorRegistry] = None,
        labels: Optional[Dict[str, str]] = None,
        prefix: str = COMPONENT_PREFIX,
    ):
        self.registry = registry or CollectorRegistry()
        self.labels = dict(labels or {})
        self.prefix = prefix
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def child(self, **labels: str) -> "MetricsRegistry":
        merged = {**self.labels, **labels}
        return MetricsRegistry(self.registry, merged, self.prefix)

    def _full_name(self, name: str) -> str:
        return name if name.startswith("dynamo_") else f"{self.prefix}{name}"

    def _get_or_create(self, kind, name: str, documentation: str, extra_labels: Iterable[str] = (), **kwargs):
        full = self._full_name(name)
        label_names = tuple(sorted(self.labels)) + tuple(extra_labels)
        key = f"{full}|{','.join(label_names)}"
        with self._lock:
            metric = self._metrics.get(key)
            if metric is None:
                try:
                    metric = kind(full, documentation, labelnames=label_names, registry=self.registry, **kwargs)
                except ValueError:
                    # Already registered on the shared registry by a sibling
                    # node — reuse the collector, but ONLY if its label set
                    # matches. Silently reusing a collector with different
                    # labels made ``.labels(**values)`` blow up far from the
                    # misdeclaring call site (or, worse, record under the
                    # wrong series).
                    metric = self.registry._names_to_collectors.get(full)  # type: ignore[attr-defined]
                    if metric is None:
                        raise
                    existing = tuple(getattr(metric, "_labelnames", ()))
                    if tuple(sorted(existing)) != tuple(sorted(label_names)):
                        raise ValueError(
                            f"metric {full!r} already registered with labels "
                            f"{sorted(existing)}, requested {sorted(label_names)}; "
                            "sibling registries must declare identical label sets "
                            "for a shared metric name"
                        )
                self._metrics[key] = metric
        return metric

    def _labelled(self, metric, extra: Dict[str, str]):
        values = {**self.labels, **extra}
        return metric.labels(**values) if values else metric

    def counter(self, name: str, documentation: str = "", **extra_labels: str):
        m = self._get_or_create(Counter, name, documentation, extra_labels=sorted(extra_labels))
        return self._labelled(m, extra_labels)

    def gauge(self, name: str, documentation: str = "", **extra_labels: str):
        m = self._get_or_create(Gauge, name, documentation, extra_labels=sorted(extra_labels))
        return self._labelled(m, extra_labels)

    def histogram(
        self,
        name: str,
        documentation: str = "",
        buckets: Optional[Tuple[float, ...]] = None,
        **extra_labels: str,
    ):
        kwargs = {"buckets": buckets} if buckets else {}
        m = self._get_or_create(Histogram, name, documentation, extra_labels=sorted(extra_labels), **kwargs)
        return self._labelled(m, extra_labels)

    def render(self) -> bytes:
        """Prometheus text exposition."""
        return generate_latest(self.registry)


# Latency histogram buckets tuned for LLM serving (TTFT ms-scale, ITL ms-scale)
# — ref: http/service/metrics.rs histogram buckets.
TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)
DURATION_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)
