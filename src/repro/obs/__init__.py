"""``repro.obs`` — stdlib-only metrics, request tracing and process
spans for the serve fleet and the engine (docs/observability.md).

Observe-only by contract: engine results never flow through this
package, instrumented runs are pinned bit-equal to uninstrumented
runs, and the whole per-request layer switches off via
``repro.obs.disable`` (or ``REPRO_OBS=0``).
"""

from .state import enabled, enable, disable, set_enabled, scoped
from .clock import to_wall, anchor
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      log_bounds, quantile, render_prometheus)
from .trace import (mint, Tracer, TRACER, set_service, to_perfetto,
                    DEFAULT_CAPACITY, span, SPANS, watch_compiles)
from . import catalog, clock, metrics, state, trace

__all__ = [
    "enabled", "enable", "disable", "set_enabled", "scoped",
    "to_wall", "anchor",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "log_bounds", "quantile", "render_prometheus",
    "mint", "Tracer", "TRACER", "set_service", "to_perfetto",
    "DEFAULT_CAPACITY", "span", "SPANS", "watch_compiles",
    "catalog", "clock", "metrics", "state", "trace",
]
