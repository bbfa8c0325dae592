"""One cell, one run:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data that this file finds by name from
`BENCHMARK.json` (README.md): the configuration's file, the family it
names under families/ (everything that knows the shape of a block: its
sizes and seeded weights, the engine built over them, the plain
reference, the operations it needs), the cell's file under workloads/,
the traffic generator it names under traffic/, and one reader per
per-layer metric under layer_metrics/.

The run: claim the chip (a TPU whose kind is in peaks.json, or exit 2
with nothing on stdout), place JAX's persistent compilation cache,
have the family build the model, its seeded weights and the program's
engine, serve one throwaway request (that compiles, or loads, the cell's
two programs) — all of that is `setup_s` — then hand the engine the cell's
requests in ONE call of `PagedEngine.run(requests, mode="continuous")`.
That call is the window. It lasts `--seconds`: every request carries
the window's close as its deadline, so what is still queued or decoding
then is swept by the engine's own expiry and the call returns. Tokens
served to a request before the close count; a request cut by the close
is neither finished nor failed.

Then: read the device's peak memory, free the engine, run the plain
reference over a sample of what was served (correct.py), and print one
JSON line. `--trace 1` traces the last seconds of the window and
prints the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.machinery  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
import zlib  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.percentile import pct_nearest  # noqa: E402

TRACE_SECONDS = 4.0     # the traced slice: the window's last seconds
EXIT_NO_CHIP = 2


class NoChip(RuntimeError):
    pass


# -- finding a cell's files by name ---------------------------------------

def load_cell(name: str, bench_file: Path):
    """(cell entry, configuration, cell's file, benchmark) by the names
    in the benchmark file. The directory that holds configs/ and
    workloads/ is the benchmark file's first `paths` entry."""
    bench = json.loads(Path(bench_file).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {bench_file}; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    base = Path(bench_file).resolve().parent
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((base / conf["file"]).read_text())
    wl = json.loads(
        (base / bench["paths"][0] / "workloads" / f"{name}.json").read_text())
    for key in ("config", "traffic"):
        if wl[key] != cell[key]:
            raise SystemExit(f"{name}: the cell's file says {key} "
                             f"{wl[key]!r}, BENCHMARK.json {cell[key]!r}")
    return cell, cfg, wl, bench


def load_named(bench_dir: Path, kind: str, name: str):
    """Import one data-named module — `traffic/<generator>.py`, a
    reader `layer_metrics/<metric>.py` — from the benchmark's directory
    (tests/tiny borrows this one's). The file's name is the
    generator's or metric's name, dots and all."""
    path = bench_dir / kind / f"{name}.py"
    return load_by_path(path if path.exists() else HERE / kind / f"{name}.py")


def load_by_path(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- a configuration's family ---------------------------------------------

FAMILY_MODULES = ("weights", "build", "reference", "work")


def families_in(bench_dir: Path) -> dict:
    """name -> directory of every family a configuration of the
    benchmark at `bench_dir` can name: this directory's families/, and
    before them its own (tests/tiny borrows these and brings one more)."""
    found = {}
    for base in (HERE, Path(bench_dir)):
        for path in sorted((base / "families").glob("*")):
            if all((path / f"{m}.py").exists() for m in FAMILY_MODULES):
                found[path.name] = path
    return found


def family_dir(cfg: dict, bench_dir: Path) -> Path:
    """Where the family that a configuration names lives. There is no
    default family: a configuration that names none, or none that is
    there, ends the run here, before anything touches the chip."""
    there = families_in(bench_dir)
    name = cfg.get("family")
    if name not in there:
        raise SystemExit(
            f"configuration {cfg.get('name')!r} names the family {name!r}; "
            f"there are {sorted(there)} (each a directory families/<family>/ "
            f"with {', '.join(m + '.py' for m in FAMILY_MODULES)})")
    return there[name]


def load_family(path: Path):
    """The family's four modules (README, "families"), imported as one
    package found by its path, so that they import each other
    (`from . import weights`) wherever the directory lies."""
    path = Path(path).resolve()
    # The package's name carries the path, so that two benchmarks in one
    # process (the tests') may each bring a family of one name.
    pkg = (f"bench_family_{zlib.crc32(str(path).encode()):08x}_"
           + re.sub(r"\W", "_", path.name))
    if pkg not in sys.modules:
        spec = importlib.machinery.ModuleSpec(pkg, None, is_package=True)
        spec.submodule_search_locations = [str(path)]
        sys.modules[pkg] = importlib.util.module_from_spec(spec)
    return types.SimpleNamespace(
        name=path.name, dir=path,
        **{m: importlib.import_module(f"{pkg}.{m}") for m in FAMILY_MODULES})


# -- the chip ---------------------------------------------------------------

def claim_chip(chips: int, require_chip: bool):
    """The device as JAX reports it, and its row of peaks.json. Without
    a TPU of a known kind, or with fewer chips than the cell needs,
    NoChip — unless a test asks to go on without one (peaks then None)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = json.loads((HERE / "peaks.json").read_text()).get(device["kind"])
    if not require_chip:
        return device, peaks
    if device["platform"] != "tpu":
        raise NoChip(f"JAX found platform {device['platform']!r}, not a TPU")
    if not isinstance(peaks, dict):
        raise NoChip(f"device kind {device['kind']!r} has no row in "
                     "benchmarks/peaks.json; add it with its source")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return device, peaks


def place_compile_cache() -> str:
    """`$JAX_COMPILATION_CACHE_DIR`, else <checkout>/.cache/jax — a fixed
    path, and every program cached however quick its compile (PR 21:
    the default threshold left 18 s of small programs to recompile)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# -- the window -------------------------------------------------------------

def make_requests(wl: dict, bench_dir: Path, *, seed, seconds, vocab):
    from mpi_cuda_cnn_tpu.serve.scheduler import Request

    gen = load_named(bench_dir, "traffic", wl["generator"])
    return [
        Request(rid=i, prompt=prompt, max_new_tokens=out, arrival=t,
                deadline=float(seconds))
        for i, (t, prompt, out) in enumerate(gen.generate(
            wl["params"], seed=seed, seconds=seconds, vocab=vocab))
    ]


def warm_up(engine, vocab: int) -> None:
    """One throwaway request through both of the cell's programs: a
    prompt of more than one chunk, and more than one decode tick."""
    import numpy as np
    from mpi_cuda_cnn_tpu.serve.scheduler import Request

    prompt = np.arange(engine.prefill_chunk + 3, dtype=np.int32) % vocab
    res = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=3)],
                     mode="continuous")
    if [r.status for r in res.requests] != ["finished"]:
        raise RuntimeError("the warm-up request did not finish")


class TraceSlice:
    """The engine's per-iteration records (`tick_sink`), and the
    profiler switched on from inside the loop when the window's last
    TRACE_SECONDS begin."""

    def __init__(self, trace_dir: Path, start_at: float):
        self.dir, self.start_at = trace_dir, start_at
        self.ticks: list[dict] = []
        self.first_traced = None     # index of the first record traced

    def sink(self, rec: dict) -> None:
        self.ticks.append(rec)
        if self.first_traced is None and rec["now"] >= self.start_at:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.first_traced = len(self.ticks)

    def stop(self):
        """Switch the profiler off and reduce what it wrote."""
        import jax

        from benchmarks import reduce_trace

        if self.first_traced is None:
            raise RuntimeError("the window ended before tracing began")
        jax.profiler.stop_trace()
        try:
            return reduce_trace.load(reduce_trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def open_window(engine, requests, seconds: float, trace: bool):
    """The timed call, `PagedEngine.run(requests, mode="continuous")`,
    once. Whatever the program raises ends the run with no result."""
    if not requests:
        raise SystemExit(f"no request is due in {seconds} s")
    slicer = None
    if trace:
        slicer = TraceSlice(ROOT / ".cache" / "bench_trace",
                            max(0.0, seconds - TRACE_SECONDS))
    result = engine.run(requests, mode="continuous",
                        tick_sink=slicer.sink if slicer else None)
    return result, slicer


# -- end-to-end metrics (the arithmetic of ServeResult.summary, copied) -----

CUT = "expired"      # the status the window's close gives a request


def outcome(requests):
    """(served tokens, failed): failed are the requests that ended in
    any way but finishing or being cut by the window's close."""
    failed = [r for r in requests
              if r.status != "finished"
              and not (r.status == CUT and r.fail_reason == "deadline")]
    return sum(len(r.out) for r in requests), failed


def end_to_end(result, seconds: float) -> dict:
    """tokens_per_s over all tokens and all of the call's time;
    tpot over every request that was served two tokens or more (to its
    last token, or to the close); ttft over every request due in the
    window (one with no token yet at the close counts its wait so far);
    a failed request counts as the whole window in both tails."""
    tokens, failed = outcome(result.requests)
    worst = 1e3 * seconds
    tpot, ttft = [worst] * len(failed), [worst] * len(failed)
    failed_rids = {r.rid for r in failed}
    for r in result.requests:
        if r.rid in failed_rids:
            continue
        if r.first_token_at is None:
            ttft.append(1e3 * (r.finished_at - r.arrival))
            continue
        ttft.append(1e3 * (r.first_token_at - r.arrival))
        if len(r.out) >= 2:
            tpot.append(1e3 * (r.finished_at - r.first_token_at)
                        / (len(r.out) - 1))
    return {
        "tokens_per_s": tokens / result.duration_s,
        "tpot_p95_ms": pct_nearest(tpot, 95),
        "ttft_p95_ms": pct_nearest(ttft, 95),
    }


# -- per-layer metrics ------------------------------------------------------

def per_layer(bench, cell_name, bench_dir: Path, ctx: dict) -> dict:
    """Every per-layer metric of this cell through its own reader. A
    reader with nothing to read returns None and is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = load_named(bench_dir, "layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def label_gaps(trace, ticks, first_traced: int):
    """The longest idle gaps, each named by the program that ran next
    and the engine's record of that iteration. Records and module runs
    pair off from the end of the window: the last record that decoded
    is the last `jit_tick`, and so on backwards."""
    traced = ticks[first_traced:]
    by_module = {
        "jit_tick": [t for t in traced if t["decoded"]],
        "jit_prefill": [t for t in traced if t["prefill"]],
    }
    runs = {n: len(trace.module_durations(n)) for n in by_module}
    out = []
    for _, seconds, name, nth in trace.gaps():
        label = f"before {name or 'the first operation'}"
        recs = by_module.get(name)
        if recs:
            i = nth - (runs[name] - len(recs))
            if 0 <= i < len(recs):
                t = recs[i]
                label += (f" (decoding {len(t['decoded'])}, running "
                          f"{t['running']}, queued {t['queue']}, admitted "
                          f"{len(t['admitted'])}, finished "
                          f"{len(t['finished'])})")
        out.append([label, seconds])
    return out


# -- one run ----------------------------------------------------------------

def prepare(cell_name: str, *, seed: int, bench_file: Path,
            require_chip: bool) -> dict:
    """Set-up: the family, the chip, the cache, the weights, the
    engine, warm."""
    cell, cfg, wl, bench = load_cell(cell_name, bench_file)
    bench_dir = Path(bench_file).resolve().parent / bench["paths"][0]
    family_at = family_dir(cfg, bench_dir)
    device, peaks = claim_chip(int(cell["chips"]), require_chip)
    place_compile_cache()

    import jax

    family = load_family(family_at)
    phases = {"reach_chip": time.perf_counter() - T_START}
    dm = family.weights.dims(cfg)
    params = family.build.serving_params(dm, seed, cfg)
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - T_START
    engine = family.build.engine_of(cfg, dm, params)
    warm_up(engine, dm["vocab"])
    phases["warm"] = time.perf_counter() - T_START
    return {
        "name": cell_name, "cell": cell, "config": cfg, "workload": wl,
        "bench": bench, "device": device, "peaks": peaks, "dims": dm,
        "family": family, "bench_dir": bench_dir,
        "engine": engine, "seed": seed, "phases": phases,
        "setup_s": phases["warm"],
    }


def measure(cell: dict, *, seconds: float, trace: bool,
            params_override: dict | None = None):
    """The window and what is read off it. Returns (metrics of the
    kind the run asked for, extras for the result line, ServeResult)."""
    import jax

    wl, dm, engine = cell["workload"], cell["dims"], cell["engine"]
    if params_override:        # sweep.py: another rate, same everything
        wl = {**wl, "params": {**wl["params"], **params_override}}
    requests = make_requests(wl, cell["bench_dir"], seed=cell["seed"],
                             seconds=seconds, vocab=dm["vocab"])
    result, slicer = open_window(engine, requests, seconds, trace)
    reduced = slicer.stop() if slicer else None
    device = dict(cell["device"])
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices())
    tokens, failed = outcome(result.requests)
    e2e = end_to_end(result, seconds)
    never = sum(r.admitted_at is None for r in result.requests)
    print(json.dumps({"info": {
        "cell": cell["name"], "seed": cell["seed"],
        "rate_rps": wl["params"]["rate_rps"], "requests_due": len(requests),
        "finished": len(result.finished_requests),
        "cut_by_close": sum(r.status == CUT for r in result.requests),
        "never_admitted": never, "failed": len(failed),
        "served_tokens": tokens, "window_s": result.duration_s,
        "decode_ticks": result.decode_ticks,
        "prefill_chunks": result.prefill_chunks,
        "preemptions": result.preemptions, **e2e,
        "set_up_phases_s": cell["phases"],
    }}), flush=True)
    bench, name = cell["bench"], cell["name"]
    extras = {}
    if trace:
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        metrics = per_layer(bench, name, cell["bench_dir"], {
            "cell": name, "config": cell["config"], "dims": dm,
            "family": cell["family"], "peaks": cell["peaks"],
            "chips": int(cell["cell"]["chips"]),
            "trace": reduced, "ticks": slicer.ticks,
            "first_traced": slicer.first_traced,
            "requests": result.requests, "window_s": result.duration_s,
            "slots": engine.slots, "max_len": engine.max_len,
            "prefill_chunk": engine.prefill_chunk,
        })
        extras["breakdown"] = {
            "device_ops": reduced.top_ops(),
            "idle_gaps": label_gaps(reduced, slicer.ticks,
                                    slicer.first_traced),
        }
    else:
        values = {**e2e, "setup_s": cell["setup_s"]}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if "workloads" not in m or name in m["workloads"]
        }
    return metrics, {"device": device, **extras}, result


def check(cell: dict, result, lower: str | None = None):
    """`correct` and the table of what was compared. Call it with the
    engine already dropped from `cell`: the reference needs the room."""
    from benchmarks import correct

    dm, wl = cell["dims"], cell["workload"]
    _, failed = outcome(result.requests)
    served = [(r.rid, r.prompt, list(r.out)) for r in result.requests
              if r.out]
    short = sum(
        1 for r in result.finished_requests
        if len(r.out) != r.max_new_tokens
        or min(r.out) < 0 or max(r.out) >= dm["vocab"])
    compared = {"short": short, "failed": len(failed), "tokens_compared": 0}
    limits = {**wl["correct"]["limits"], "short": 0, "failed": 0}
    control = None
    sample = correct.pick_sample(served, cell["seed"])
    if sample:
        gaps = correct.gaps(cell["family"].reference.forward_logits, dm,
                            cell["seed"], sample,
                            int(cell["config"]["max_len"]), lower)
        compared.update(correct.numbers(gaps["served"]))
        compared["tokens_compared"] = len(gaps["served"])
        if lower:
            # The control in the program's place, through the same
            # judge and the same limits: it has to come out not correct.
            ok, table = correct.judge(
                {**compared, **correct.numbers(gaps["lower"])}, limits)
            control = {"lower": lower, "correct": ok, "compared": table}
    ok, table = correct.judge(compared, limits)
    need = wl["correct"]["min_tokens_compared"]
    table["tokens_compared"] = {"value": compared["tokens_compared"],
                                "at_least": need}
    return ok and compared["tokens_compared"] >= need, table, control


def run_cell(cell_name: str, *, seed: int, seconds: float, trace: bool,
             bench_file: Path = ROOT / "BENCHMARK.json",
             require_chip: bool = True, lower: str | None = None) -> dict:
    """The whole run; returns the result line as a dict. `lower` also
    judges the control (limits.py and the tests; no benchmark run does)."""
    cell = prepare(cell_name, seed=seed, bench_file=bench_file,
                   require_chip=require_chip)
    metrics, extras, result = measure(cell, seconds=seconds, trace=trace)
    # The peak is read; the program's state goes before the reference.
    del cell["engine"]
    gc.collect()
    t0 = time.perf_counter()
    ok, table, control = check(cell, result, lower)
    print(json.dumps({"reference_s": time.perf_counter() - t0}), flush=True)
    line = {"correct": bool(ok), "attempted": len(result.requests),
            "failed": table["failed"]["value"], "metrics": metrics, **extras}
    if control:
        line["control"] = control
    line["compared"] = table
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace))
    except NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, row in line["compared"].items():
        print(f"compared {name}: {json.dumps(row)}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
