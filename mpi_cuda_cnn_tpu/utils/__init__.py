"""Utilities: config/flags, logging/metrics, profiling."""

from .config import Config, parse_args
from .logging import MetricsLogger, get_logger
from .profiling import StepTimer, profile_trace

__all__ = [
    "Config",
    "parse_args",
    "MetricsLogger",
    "get_logger",
    "StepTimer",
    "profile_trace",
]
