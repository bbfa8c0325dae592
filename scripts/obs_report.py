"""Aggregate metrics JSONL run(s) into markdown tables (or JSON).

The script twin of `mctpu report` — one implementation (obs/report.py),
two entry points:

    python scripts/obs_report.py run.jsonl [--format md|json]
                                           [--peak-tflops 197]

Reads any file of obs.schema records; '#' comment lines and pre-schema
rows (pre-schema capture files) pass through without validation.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_cuda_cnn_tpu.obs.report import report_main

if __name__ == "__main__":
    raise SystemExit(report_main())
