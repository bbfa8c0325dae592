"""Compiled-program accounting: FLOPs/bytes/collectives from the program
XLA actually runs.

Before this module, MFU numerators were analytic formulas
(train/lm.lm_flops_per_token) or hand-derived constants inside bench
scripts, and collective behavior was asserted from reading the source.
Here both are computed properties of the compiled step:

- `analyze(jitted_fn, *args)` lowers + compiles the function for the
  given arguments and reads `cost_analysis()` — FLOPs and bytes of the
  real post-fusion program, the same numbers XProf's roofline uses.
- Collectives are counted two ways, because they appear at two levels:
  `jaxpr_collective_counts` walks the jaxpr (explicit collectives the
  program writes itself — shard_map psum/ppermute/all_to_all), and
  `hlo_collective_counts` scans the compiled HLO (which ALSO includes
  whatever GSPMD inserted). The HLO count is the ground truth for "what
  crosses the interconnect per step"; the jaxpr count is the structural
  check tests pin.

Caveats, so numbers are read honestly: `cost_analysis` reports the
per-module optimized-HLO estimate (per-core on multi-device backends),
and it counts STATIC HLO — a `lax.scan`/`while` body is counted ONCE,
not per trip (measured: a 10-iteration scan of a matmul reports the
same FLOPs as 1 iteration). For a scanned-epoch program the reported
FLOPs are therefore ~one step's, not the dispatch's; producers record
that with `counting="static-body"` and `steps_per_dispatch=1` so
downstream per-step math stays correct. The same staticness applies to
collective counts (a psum inside the scan body counts 1, executes N
times). Finally, `lower().compile()` does not share jit's executable
cache in all JAX versions, so `analyze` can cost one extra compile —
callers on hot paths do it once per program shape and keep it out of
their timing envelopes (StepTimer.exclude).
"""

from __future__ import annotations

import dataclasses
import re

import jax

# Peak dense-matmul TFLOP/s per chip — the MFU denominator — keyed by
# the `device_kind` string JAX reports for the chip (taken from the chip
# run of PR 21, not typed from memory). The ONE table; a chip that is
# not in it is an error (`peak_flops`), never a default, so an MFU can
# never be computed against another chip's peak.
#   "TPU v5 lite": bfloat16 from Google Cloud documentation, "TPU v5e"
#   (197 TFLOP/s bf16 per chip); float32 is bf16 / 4, the MXU's
#   multi-pass f32 path (no published figure).
PEAK_TFLOPS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bfloat16": 197.0, "float32": 49.25},
}

# Jaxpr primitive names that are cross-device collectives.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter",
})

# HLO instruction names that are collectives (async forms appear as
# NAME-start/NAME-done pairs — counting '-start' or the bare name, and
# never '-done', counts each collective once).
_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|collective-permute"
    r"|reduce-scatter)(-start)?\("
)


@dataclasses.dataclass
class ProgramCosts:
    """Accounting for ONE compiled program (which may run many train
    steps per dispatch — scanned epochs; `flops` is per dispatch).

    The alias/memory fields are the donation ledger: `aliased_outputs`
    counts entries in the compiled HLO's input_output_alias table (one
    per donated buffer XLA actually aliased), `alias_bytes` is their
    total size, and `temp_bytes` the program's live scratch — together
    the mechanical proof that donate_argnums took effect (a shape or
    layout mismatch silently degrades donation to a copy). All None when
    the backend exposes no memory analysis."""

    flops: float | None
    bytes_accessed: float | None
    collectives: dict[str, int]
    aliased_outputs: int = 0
    alias_bytes: float | None = None
    temp_bytes: float | None = None
    output_bytes: float | None = None
    argument_bytes: float | None = None

    def to_fields(self) -> dict:
        """The record fields a "program" event carries (obs.schema)."""
        return {
            "flops": self.flops,
            "bytes": self.bytes_accessed,
            "collectives": self.collectives,
            "aliased_outputs": self.aliased_outputs,
            "alias_bytes": self.alias_bytes,
            "temp_bytes": self.temp_bytes,
        }


def peak_flops(dtype: str = "bfloat16", *, device_kind: str | None = None,
               override_tflops: float | None = None) -> float | None:
    """Peak FLOP/s for the MFU denominator. `device_kind` defaults to
    the running device's. CPU has no meaningful MXU peak: None, and MFU
    reports null rather than a number against a fake one. Any other
    kind must be in PEAK_TFLOPS — an unknown chip raises. An override
    names the chip's bf16 peak; its f32 peak is a quarter of that (the
    MXU's multi-pass f32 path)."""
    f32 = dtype not in ("bfloat16", "bf16")
    if override_tflops is not None:
        return override_tflops * 1e12 / (4 if f32 else 1)
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind == "cpu":
        return None
    if device_kind not in PEAK_TFLOPS:
        raise ValueError(
            f"no peak FLOP/s registered for device kind {device_kind!r}; "
            "add it to obs.cost.PEAK_TFLOPS with its source (known: "
            f"{sorted(PEAK_TFLOPS)})"
        )
    return PEAK_TFLOPS[device_kind]["float32" if f32 else "bfloat16"] * 1e12


def mfu(flops: float | None, seconds: float, peak: float | None) -> float | None:
    """Model FLOPs utilization; None whenever a factor is unknown."""
    if not flops or not peak or seconds <= 0:
        return None
    return flops / seconds / peak


def hlo_collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective instructions in compiled HLO text."""
    counts: dict[str, int] = {}
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text):
        name = m.group(1)
        counts[name] = counts.get(name, 0) + 1
    return counts


def _walk_jaxpr(jaxpr, counts: dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            counts[name] = counts.get(name, 0) + 1
        for v in eqn.params.values():
            # Recurse into sub-jaxprs (jit/scan/while/cond/shard_map
            # bodies) wherever they appear in the params tree.
            for sub in jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "jaxpr") or hasattr(x, "eqns")
            ):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, counts)


def jaxpr_collective_counts(fn, *args, **kwargs) -> dict[str, int]:
    """Count explicit collective primitives in fn's jaxpr (static count:
    a ppermute inside a scan body counts once, not per iteration)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: dict[str, int] = {}
    _walk_jaxpr(closed.jaxpr, counts)
    return counts


def hlo_alias_count(hlo_text: str) -> int:
    """Number of input->output buffer aliases in a compiled module — the
    entries of the header's `input_output_alias={ {i}: (p, {}, kind) }`
    table, each tagged `may-alias` or `must-alias`. 0 means donation
    (if requested) was dropped entirely."""
    head = hlo_text.split("\n", 1)[0]
    return head.count("may-alias") + head.count("must-alias")


def _memory_fields(compiled) -> dict:
    """alias/temp/output/argument bytes from XLA memory analysis; {} when
    the backend doesn't expose it."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for field, attr in (
        ("alias_bytes", "alias_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("argument_bytes", "argument_size_in_bytes"),
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[field] = float(v)
    return out


def analyze(fn, *args, **kwargs) -> ProgramCosts:
    """Lower + compile `fn` for these args and read the XLA accounting.

    `fn` must be jit-wrapped (anything with .lower — jax.jit output).
    Raises whatever lowering/compilation raises; use `try_analyze` on
    paths that must never fail for telemetry's sake.
    """
    compiled = fn.lower(*args, **kwargs).compile()
    costs = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    return ProgramCosts(
        flops=costs.get("flops"),
        bytes_accessed=costs.get("bytes accessed"),
        collectives=hlo_collective_counts(hlo),
        aliased_outputs=hlo_alias_count(hlo),
        **_memory_fields(compiled),
    )


def tree_bytes(tree) -> int:
    """Total bytes of a pytree of arrays (the donatable size of a state
    argument — the denominator assert_donation checks alias_bytes
    against)."""
    return sum(
        int(getattr(l, "nbytes", 0))
        for l in jax.tree_util.tree_leaves(tree)
    )


def donation_report(fn, *args, **kwargs) -> dict | None:
    """Compile fn(*args) and report whether its donated argument 0 (the
    state pytree, by the repo-wide donate_jit convention) was actually
    aliased: {"aliased_outputs", "alias_bytes", "state_bytes",
    "fraction"}. None when the backend resists AOT analysis."""
    costs = try_analyze(fn, *args, **kwargs)
    if costs is None:
        return None
    state_bytes = tree_bytes(args[0]) if args else 0
    alias = costs.alias_bytes
    return {
        "aliased_outputs": costs.aliased_outputs,
        "alias_bytes": alias,
        "state_bytes": state_bytes,
        "fraction": (
            alias / state_bytes if alias is not None and state_bytes else None
        ),
    }


def assert_donation(fn, *args, min_fraction: float = 0.9, label: str = "step",
                    **kwargs) -> dict:
    """The compile-time donation guard: raise unless at least
    `min_fraction` of the state argument's bytes are input/output-aliased
    in the compiled program. Small unaliased leaves (a scalar step
    counter XLA folds, adamw's count) are why the bar is a byte fraction,
    not a leaf count. Returns the donation_report on success; raises
    RuntimeError when analysis is unavailable (a guard that silently
    passes is no guard)."""
    rep = donation_report(fn, *args, **kwargs)
    if rep is None:
        raise RuntimeError(
            f"{label}: donation guard could not analyze the compiled "
            "program on this backend"
        )
    frac = rep["fraction"]
    if rep["aliased_outputs"] and frac is None:
        # The HLO alias table proves donation took effect but the
        # backend exposes no memory_analysis() to size it — that is
        # missing ACCOUNTING, not dropped donation; report it as the
        # unavailable-analysis case the docstring promises.
        raise RuntimeError(
            f"{label}: donation happened ({rep['aliased_outputs']} "
            "aliased outputs) but this backend exposes no memory "
            "analysis to check the byte fraction"
        )
    if not rep["aliased_outputs"] or frac is None or frac < min_fraction:
        raise AssertionError(
            f"{label}: expected >= {min_fraction:.0%} of the state's "
            f"{rep['state_bytes']} bytes aliased input->output, got "
            f"{rep['alias_bytes']} over {rep['aliased_outputs']} aliases "
            "— donation was dropped (donate flag off, or an output "
            "shape/layout mismatch degraded it to a copy)"
        )
    return rep


def try_analyze(fn, *args, **kwargs) -> ProgramCosts | None:
    """analyze(), or None if anything about this backend/function resists
    AOT lowering — telemetry must degrade, not break the train loop."""
    try:
        return analyze(fn, *args, **kwargs)
    except Exception:
        return None


def log_program(metrics, label: str, fn, *args,
                steps_per_dispatch: int = 1,
                counting: str = "program",
                compute_dtype: str = "float32") -> bool:
    """Analyze `fn(*args)` and emit ONE "program" record to `metrics`
    (a utils.logging.MetricsLogger). Returns False when analysis failed
    — the ONE emit path both trainers share, so the record shape cannot
    drift between them.

    counting="static-body" marks a scanned program whose body XLA counts
    once (see module docstring): such producers pass
    steps_per_dispatch=1 so flops stay ~per-step."""
    costs = try_analyze(fn, *args)
    if costs is None:
        return False
    metrics.log(
        "program", label=label, steps_per_dispatch=steps_per_dispatch,
        counting=counting, backend=jax.default_backend(),
        device_kind=jax.devices()[0].device_kind,
        compute_dtype=compute_dtype, **costs.to_fields(),
    )
    return True
