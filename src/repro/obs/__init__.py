"""``repro.obs`` -- unified observability: metrics, tracing, exposition.

The measurement substrate for solves, kernels, and the serving plane
(ROADMAP: the management-plane counterpart to the PR 8 service).  Three
layers, all host-side (an instrumented solve is bitwise identical to a
bare one -- asserted in ``tests/test_obs.py``), plus the one piece that
lives in the device program, as metadata only:

* :mod:`repro.obs.metrics` -- process-local registry (:data:`REGISTRY`)
  of labeled counters, gauges and log-bucket histograms; cheap enough to
  leave always-on, with :func:`set_enabled` / :func:`disabled` as the
  kill switch the overhead benchmark measures against.
* :mod:`repro.obs.trace` -- span ring buffer (:data:`TRACER`): solve
  (with its stage-in / execute / stage-out children) / chunk / plan-build
  / plan-compile / tick spans with parent and request ids, Chrome
  trace-event export, optional ``jax.profiler`` bridge.
* :mod:`repro.obs.export` -- Prometheus text exposition, JSON snapshots,
  and the stdlib HTTP ``/metrics`` endpoint
  (``launch/serve.py --metrics-port``).
* :mod:`repro.obs.scopes` -- the closed vocabulary of ``jax.named_scope``
  layers the solve programs are built under (gather, matvec, precond,
  update, reduce, halo, control, and the multigrid's smooth and transfer)
  and :data:`SCOPES`, the HLO
  instruction -> scope map of every compiled plan, which names a
  profiler trace's device operations by layer.

Plus :mod:`repro.obs.clock`: the ONE injectable monotonic clock every
host-side timing path reads (``serve``, ``ft``, the load generator) --
install a :class:`~repro.obs.clock.FakeClock` and deadline/straggler
logic becomes deterministic in tests.

Quickstart::

    from repro import obs
    obs.REGISTRY.counter("my_events_total", "things that happened").inc()
    with obs.span("phase", kind="solve", matrix="lap2d_32"):
        ...
    print(obs.render_prometheus())          # or serve it:
    srv = obs.start_metrics_server(port=9100)
"""

from . import clock
from .export import (
    MetricsServer,
    render_prometheus,
    snapshot,
    start_metrics_server,
)
from .metrics import (
    DEFAULT_LATENCY_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    disabled,
    enabled,
    log_buckets,
    set_enabled,
)
from .trace import TRACER, Span, Tracer, set_jax_bridge, span

__all__ = [
    "clock",
    # metrics
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "log_buckets", "DEFAULT_LATENCY_BUCKETS",
    "enabled", "set_enabled", "disabled",
    # tracing
    "Span", "Tracer", "TRACER", "span", "set_jax_bridge",
    # exposition
    "render_prometheus", "snapshot", "MetricsServer", "start_metrics_server",
]
