from .export import (AdminServer, attach_serving_engine,  # noqa: F401
                     live_admin_servers, render_prometheus, serve_admin)
from .monitor import MonitorMaster, events_from_scalars  # noqa: F401
from .perf import (CompileLedger, CompiledProgram,  # noqa: F401
                   PerfAccounting, ProgramRegistry, SetupRecord,
                   compile_ledger, device_memory_stats, device_peaks,
                   live_program_table)
from .registry import (Counter, Gauge, Histogram,  # noqa: F401
                       MetricsRegistry)
from .tracing import (FlightRecorder, NULL_TRACER, Tracer,  # noqa: F401
                      configure, flight_dump, get_tracer, validate_event)
