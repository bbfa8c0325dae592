"""obs — the telemetry subsystem.

Four pillars, one record schema:

- `trace`:  spans/annotations with ONE naming scheme across XProf device
            traces and the JSONL metrics stream.
- `cost`:   FLOPs/bytes/collectives of the COMPILED step via XLA cost
            analysis and HLO/jaxpr walks — MFU as a computed property,
            not a hand-typed constant.
- `device`: HBM occupancy/peaks from device.memory_stats(), degrading
            to None on backends without allocator stats.
- `schema`: the versioned JSONL record shape shared by MetricsLogger,
            bench.py, and `mctpu report`; `report` renders any run file
            into the markdown tables PERF.md used to assemble by hand.

Plus the SLO layer on top of the schema (ISSUE 8): `slo` (declarative
per-tenant objectives, error budgets, multi-window burn-rate math),
`alerts` (the streaming rule engine whose live and replayed sequences
are bitwise-identical), and `health` (`mctpu health` — per-tenant
verdict tables with a CI exit code).
"""

from .cost import (  # noqa: F401
    COLLECTIVE_PRIMS,
    PEAK_TFLOPS,
    ProgramCosts,
    analyze,
    hlo_collective_counts,
    jaxpr_collective_counts,
    mfu,
    peak_flops,
    try_analyze,
)
from .device import (  # noqa: F401
    device_memory_stats,
    hbm_peak_bytes,
    memory_snapshot,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_bucket_bounds,
    percentiles_from_record,
)
from .alerts import AlertEngine, alerts_crc  # noqa: F401
from .health import evaluate as evaluate_health  # noqa: F401
from .health import health_main  # noqa: F401
from .report import render_markdown, report_main, summarize  # noqa: F401
from .slo import Objective, SLOSpec  # noqa: F401
from .schema import (  # noqa: F401
    RUN_MARKER,
    SCHEMA_VERSION,
    dump_records,
    iter_records,
    iter_runs,
    load_records,
    make_record,
    validate_record,
)
from .trace import PhaseSpans, annotate, current_path, span  # noqa: F401
