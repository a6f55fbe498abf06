from tensorlink_tpu.runtime.flight import (  # noqa: F401
    FlightRecorder,
    HealthState,
    Watchdog,
    default_recorder,
    install_crash_handler,
    write_postmortem,
)
from tensorlink_tpu.runtime.mesh import MeshRuntime, make_mesh  # noqa: F401
from tensorlink_tpu.runtime.metrics import (  # noqa: F401
    Histogram,
    Metrics,
)
from tensorlink_tpu.runtime.tracing import (  # noqa: F401
    Span,
    Tracer,
    current_span,
    current_trace_context,
    straggler_report,
)
