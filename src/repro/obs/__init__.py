"""Observability: the flight recorder for the Gram service (DESIGN.md §14).

Three layers, one timeline, and two process hooks:

- ``trace``   — request-scoped spans + instant events in a bounded ring
                buffer; Chrome trace-event JSON (Perfetto-loadable) and
                JSONL export.  Near-zero cost when disabled.
- ``metrics`` — process-wide registry of counters / gauges /
                log-bucketed histograms with (bucket, dtype, gram_of,
                scheme, rung) labels; Prometheus-style text snapshots.
- ``drift``   — online cost-model drift detection: EWMA of the
                measured/predicted ratio per (bucket, winner), findings
                when a bucket leaves the ``[1/theta, theta]`` band.
- ``hooks``   — JAX compiles (registry counters; ``compile`` records
                while tracing) and garbage-collector passes (``gc``
                spans while tracing), installed at import.

The paper's claims are quantitative (2/7·n^log2(7) products, minimal
messages); ``cost_model`` / ``ata_traffic_model`` predict them, and this
package makes the prediction-vs-reality comparison a continuously
running, inspectable part of the serving stack.
"""
from . import drift, hooks, metrics, trace  # noqa: F401
from .drift import DriftDetector, DriftFinding  # noqa: F401
from .metrics import (  # noqa: F401
    MetricsRegistry, counter, gauge, histogram, get_registry,
    render_prometheus, snapshot,
)
from .trace import (  # noqa: F401
    Tracer, get_tracer, set_tracer, span, instant, add_span,
    tracing_enabled,
)

hooks.install()

__all__ = [
    "trace", "metrics", "drift", "hooks",
    "Tracer", "get_tracer", "set_tracer", "span", "instant", "add_span",
    "tracing_enabled",
    "MetricsRegistry", "counter", "gauge", "histogram", "get_registry",
    "render_prometheus", "snapshot",
    "DriftDetector", "DriftFinding",
]
