"""compile_ms_in_window (ms) — serving host loop; moves tokens_per_s.

Source: the tick records' `stops` of kind `compile`: every jax trace,
lowering and backend compile inside the run (jax.monitoring's time
spans, mapped onto the run's clock), the engine's programs and any
small jitted function alike. The reading is the length of their union
from the first record's first span to the last record's end, in ms (0
where none ran): what the warm-up missed, where
programs_compiled_in_window sees the engine's own programs only.
"""

from benchmarks import host_parts


def read(ctx):
    return host_parts.stop_ms(ctx["ticks"], "compile")
