from .metrics import (Registry, Counter, Gauge, Histogram, default_registry,  # noqa: F401
                      start_http_server)
from .tb import ScalarLogger  # noqa: F401
from .profile import trace, annotate, maybe_trace, trace_files  # noqa: F401
from . import tracing  # noqa: F401  (record-level trace context + spans)
