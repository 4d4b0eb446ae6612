"""Telemetry substrate: per-stage timing records, request-scoped spans on
the profiler's clock (telemetry/spans.py), the memory-budget simulator
standing in for the browser's WebGL limits, and the statistical analysis
used to regenerate the paper's Tables V–VIII."""

from repro.telemetry.record import StageTimes, TelemetryRecord, TelemetryLog
from repro.telemetry.budget import MemoryBudget, BudgetExceeded

__all__ = [
    "StageTimes",
    "TelemetryRecord",
    "TelemetryLog",
    "MemoryBudget",
    "BudgetExceeded",
]
