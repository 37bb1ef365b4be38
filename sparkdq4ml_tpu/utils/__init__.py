from . import faults, observability
from .logging import configure_logging, format_kv
from .observability import METRICS, TRACER, metrics_snapshot, prometheus_text
from .profiling import block_until_ready, counters
from .recovery import (RECOVERY_LOG, CircuitBreaker, CircuitOpenError,
                       DeadlineExceeded, FitFailure, RecoveryEvent,
                       RecoveryLog, RetryPolicy, check_finite, fit_or_resume,
                       recovery_events, resilient_call, retry)
