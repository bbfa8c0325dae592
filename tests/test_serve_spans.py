"""Phase spans of `PagedEngine.run` (ISSUE 25): every iteration's host
time by what the host was doing, on the tick record and on the
profiler's host track; the `compiled` counter; the idle branch's clock
repair; and the benchmark's nine readers that turn the spans into
per-layer metrics, each on hand-written records with known answers.
Since ISSUE 36 also a phase's parts, the run's stops (generation-2
collections, jax compiles) and the six readers of them."""

import gc
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.faults import FakeClock
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.obs.schema import make_record, validate_record
from mpi_cuda_cnn_tpu.obs.trace import PhaseSpans
from mpi_cuda_cnn_tpu.serve import engine as engine_mod
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.scheduler import Request

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))       # `benchmarks` is a package of the checkout

from benchmarks.run import load_by_path  # noqa: E402

MODEL = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)


class TickingClock:
    """Advances 1 ms on every read, and counts the reads: no two
    stamps coincide, so a span boundary in the wrong place shows."""

    def __init__(self):
        self.reads, self.now = 0, 0.0

    def __call__(self) -> float:
        self.reads += 1
        self.now += 0.001
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture(scope="module")
def params():
    return MODEL.init(jax.random.key(0))


def make_engine(params, **kw):
    return PagedEngine(MODEL, params, slots=2, num_pages=16, page_size=8,
                       prefill_chunk=8, **kw)


def requests(gap: float = 0.0, n: int = 3):
    """Prompts of 12 tokens = a mid-prompt chunk and a completing one."""
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, 13, 12).astype(np.int32),
                    max_new_tokens=4, arrival=gap * i) for i in range(n)]


def serve(engine, reqs, clock, **kw):
    ticks = []
    res = engine.run(reqs, time_fn=clock, sleep_fn=clock.advance,
                     tick_sink=ticks.append, **kw)
    return res, ticks


# The order an iteration runs its phases in (engine.run's docstring).
ORDER = re.compile(
    r"schedule prefill\.build( prefill\.dispatch( prefill\.wait emit)?)? grow"
    r"( tick\.build tick\.dispatch tick\.wait emit)?( idle)? bookkeep record")


@pytest.mark.parametrize("mode,gap", [("continuous", 0.0), ("static", 0.0),
                                      ("continuous", 0.2)])
def test_spans_tile_every_iteration(params, mode, gap):
    _, ticks = serve(make_engine(params), requests(gap), TickingClock(),
                     mode=mode)
    assert ticks
    before = 0.0
    for t in ticks:
        spans = t["spans"]
        assert ORDER.fullmatch(" ".join(n for n, _, _ in spans)), spans
        for (_, a, b), (_, a2, _) in zip(spans, spans[1:]):
            assert a < b == a2          # no overlap, no hole
        # Inside [the record before's now, this record's now] — but
        # for `record`, which begins at `now` and ends before the sink.
        assert spans[0][1] >= before
        assert spans[-1][0] == "record" and spans[-1][1] == t["now"]
        before = t["now"]
    if gap:
        assert any(n == "idle" for t in ticks for n, _, _ in t["spans"])


def test_wait_on_a_completing_chunk_only(params):
    _, ticks = serve(make_engine(params), requests(), TickingClock())
    kinds = set()
    for t in ticks:
        names = [n for n, _, _ in t["spans"]]
        if not t["prefill"]:
            assert "prefill.dispatch" not in names
            continue
        completing = t["prefill"][-1] == "emit"
        kinds.add(completing)
        assert "prefill.dispatch" in names
        assert ("prefill.wait" in names) == completing
    assert kinds == {True, False}


# Clock reads of the parent commit's loop (341d98c) on `requests()`
# with no sink and no registry, counted there with TickingClock.
PARENT_CLOCK_READS = {"continuous": 44, "static": 55}


def time_span_listeners():
    from jax._src import monitoring

    return monitoring.get_event_time_span_listeners()


@pytest.mark.parametrize("mode", ["continuous", "static"])
def test_bare_run_records_nothing_and_reads_the_clock_as_the_parent(
        params, mode, monkeypatch):
    def no_recorder(*a, **kw):
        raise AssertionError("a run nobody listens to built a recorder")

    def no_sync(*a, **kw):
        raise AssertionError("a run nobody listens to waited apart")

    def no_listener(*a, **kw):
        raise AssertionError("a run nobody listens to heard compiles")

    monkeypatch.setattr(engine_mod, "PhaseSpans", no_recorder)
    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    monkeypatch.setattr(jax.monitoring, "register_event_time_span_listener",
                        no_listener)
    hooks = list(gc.callbacks), time_span_listeners()

    class Watched(TickingClock):
        def __call__(self):     # no collection hook at any read
            assert gc.callbacks == hooks[0]
            return super().__call__()

    clock = Watched()
    res = make_engine(params).run(requests(), mode=mode, time_fn=clock,
                                  sleep_fn=clock.advance)
    assert all(r.status == "finished" for r in res.requests)
    assert clock.reads == PARENT_CLOCK_READS[mode]
    assert (gc.callbacks, time_span_listeners()) == hooks


def test_parts_lie_inside_their_phase_and_carry_its_name(params):
    _, ticks = serve(make_engine(params), requests(), TickingClock())
    seen = set()
    for t in ticks:
        assert {"parts", "gc_s", "stops"} <= set(t)
        phases = {n: (a, b) for n, a, b in t["spans"]}
        for name, a, b in t["parts"]:
            phase, part = name.rsplit("/", 1)
            lo, hi = phases[phase]
            assert lo < a < b < hi, (name, phases[phase])
            seen.add(name)
        # one tables and one puts part a dispatch, a fetch a wait
        names = [n for n, _, _ in t["parts"]]
        for phase in ("prefill", "tick"):
            ran = f"{phase}.dispatch" in phases
            assert names.count(f"{phase}.build/tables") == ran
            assert names.count(f"{phase}.build/puts") == ran
            assert (names.count(f"{phase}.wait/fetch")
                    == (f"{phase}.wait" in phases))
        assert names.count("bookkeep/check") == 1
    assert seen == {"prefill.build/tables", "prefill.build/puts",
                    "prefill.wait/fetch", "tick.build/tables",
                    "tick.build/puts", "tick.wait/fetch", "bookkeep/check"}


def test_fetch_reads_a_ready_result_inside_the_wait(params, monkeypatch):
    """With a recorder the wait for the tokens and their copy are two
    things: every fetch part begins after a block_until_ready."""
    order = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (order.append("ready"), real(x))[1])
    made = []

    class Kept(PhaseSpans):
        def part(self, name):
            order.append(name)
            return super().part(name)

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(engine_mod, "PhaseSpans", Kept)
    serve(make_engine(params), requests(), TickingClock())
    fetches = [i for i, n in enumerate(order) if n == "fetch"]
    assert fetches and all(order[i - 1] == "ready" for i in fetches)
    assert order.count("ready") == len(fetches)


def test_a_forced_collection_is_a_gc_stop(params):
    """A collection in the sink (between two records) lands on the
    next record: its seconds in `gc_s[2]`, itself in `stops`."""
    ticks = []

    def sink(rec):
        ticks.append(rec)
        if rec["tick"] == 1:
            gc.collect()

    clock = TickingClock()
    make_engine(params).run(requests(), time_fn=clock,
                            sleep_fn=clock.advance, tick_sink=sink)
    after = ticks[2]
    gcs = [s for s in after["stops"] if s[0] == "gc"]
    assert gcs and all(s[3] == 2 and s[1] < s[2] for s in gcs)
    assert ticks[1]["spans"][-1][2] < gcs[0][1] < after["spans"][0][1]
    assert after["gc_s"][2] == pytest.approx(
        sum(b - a for _, a, b, _ in gcs), abs=1e-5)


def test_a_new_jitted_function_is_a_compile_stop_by_name(params):
    ticks = []

    def fresh_probe_fn(x):
        return x * 3 + 1

    def sink(rec):
        ticks.append(rec)
        if rec["tick"] == 1:
            jax.jit(fresh_probe_fn)(np.arange(5))

    make_engine(params).run(requests(), tick_sink=sink)
    stops = [s for s in ticks[2]["stops"] if s[0] == "compile"]
    named = {s[3] for s in stops if "fresh_probe_fn" in s[3]}
    assert {n.split(":")[0] for n in named} == {
        "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"}
    # on the run's clock: after the record before it, before its own end
    for _, a, b, _ in stops:
        assert ticks[1]["spans"][-1][2] - 0.05 < a <= b < \
            ticks[2]["spans"][-1][2]


def test_a_recorded_run_takes_its_hooks_down(params, monkeypatch):
    hooks = list(gc.callbacks), time_span_listeners()
    serve(make_engine(params), requests(), TickingClock())
    assert (gc.callbacks, time_span_listeners()) == hooks
    engine = make_engine(params)

    def broken_tick(dslots):
        raise RuntimeError("injected")

    monkeypatch.setattr(engine, "run_decode_tick", broken_tick)
    with pytest.raises(RuntimeError, match="injected"):
        serve(engine, requests(), TickingClock())
    assert (gc.callbacks, time_span_listeners()) == hooks


def test_same_tokens_and_state_crc_with_and_without_a_sink(params):
    engine = make_engine(params)
    bare = engine.run(requests(0.2), time_fn=(c := FakeClock()),
                      sleep_fn=c.advance)
    heard, ticks = serve(engine, requests(0.2), FakeClock())
    assert heard.state_crc == bare.state_crc
    assert ([r.out for r in heard.requests]
            == [r.out for r in bare.requests])
    assert heard.decode_ticks == bare.decode_ticks == sum(
        bool(t["decoded"]) for t in ticks)


def test_spans_deterministic_and_schema_valid_under_fake_clock(params):
    engine = make_engine(params)
    _, first = serve(engine, requests(0.2), FakeClock())
    _, again = serve(engine, requests(0.2), FakeClock())
    assert [t["spans"] for t in first] == [t["spans"] for t in again]
    for t in first:
        validate_record(make_record("tick", t["now"], **t))


def test_idle_branch_judges_what_is_due_by_admits_stamp(params):
    """The parent re-read the clock in the idle branch: a request that
    fell due between admit()'s read and that one had been refused by
    nobody, and raised "cannot be admitted into an idle engine"."""
    clock = TickingClock()
    req = Request(rid=0, prompt=np.arange(12, dtype=np.int32) % 13,
                  max_new_tokens=2, arrival=0.0045)
    res = make_engine(params).run([req], time_fn=clock,
                                  sleep_fn=lambda s: None)
    assert res.requests[0].status == "finished"


def test_watchdog_window_comes_from_the_spans_stamps(params):
    res, ticks = serve(make_engine(params), requests(), TickingClock(),
                       watchdog_s=1e-9)
    slow = {e["tick"]: e["seconds"] for e in res.events
            if e["kind"] == "watchdog_slow_tick"}
    assert len(slow) == len(ticks) == res.watchdog_slow_ticks
    for t in ticks:
        start = t["spans"][0][1]
        end = next(a for n, a, _ in t["spans"] if n in ("idle", "bookkeep"))
        assert slow[t["tick"]] == pytest.approx(end - start, abs=1e-4)


def test_compiled_counter_steps_when_a_program_compiles(params):
    engine = make_engine(params)      # fresh: nothing compiled yet
    _, ticks = serve(engine, requests(), FakeClock())
    counts = [t["compiled"] for t in ticks]
    # Counted from before the run's first dispatch: the first
    # iteration's own compile (the prefill program) is a step too.
    assert counts == sorted(counts) and counts[0] >= 1
    assert counts[-1] == engine.compiled_programs() == 2   # prefill, tick
    _, warm = serve(engine, requests(), FakeClock())
    assert {t["compiled"] for t in warm} == {0}


def test_stand_ins_for_the_device_path_keep_their_one_argument_form(
        params, monkeypatch):
    """A test's fault wrapper (benchmarks/tests/test_correct.py) takes
    the slots and nothing else, traced or not: the recorder is the
    engine's for the length of run(), not an argument."""
    engine = make_engine(params)
    real = engine.run_decode_tick
    monkeypatch.setattr(engine, "run_decode_tick", lambda dslots: real(dslots))
    res, ticks = serve(engine, requests(), TickingClock())
    assert all(r.status == "finished" for r in res.requests)
    assert any("tick.wait" in [n for n, _, _ in t["spans"]] for t in ticks)
    assert engine._spans is None        # and gone once run() returns


def test_a_run_that_raises_closes_its_open_phase(params, monkeypatch):
    made = []

    class Kept(PhaseSpans):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    def broken_tick(dslots):
        raise RuntimeError("injected")

    monkeypatch.setattr(engine_mod, "PhaseSpans", Kept)
    engine = make_engine(params)
    monkeypatch.setattr(engine, "run_decode_tick", broken_tick)
    with pytest.raises(RuntimeError, match="injected"):
        serve(engine, requests(), TickingClock())
    (rec,) = made
    assert rec._ann is None and rec._spans[-1][0] == "tick.build"
    assert rec._spans[-1][2] is not None
    assert engine._spans is None


def test_a_failed_pool_check_still_delivers_the_iterations_record(
        params, monkeypatch):
    from mpi_cuda_cnn_tpu.serve.scheduler import ContinuousScheduler

    calls = []

    def check_changed(self):
        calls.append(1)
        assert len(calls) < 3, "pool broken"

    monkeypatch.setattr(ContinuousScheduler, "check_changed", check_changed)
    ticks = []
    clock = TickingClock()
    with pytest.raises(AssertionError, match="pool broken"):
        make_engine(params).run(requests(), time_fn=clock,
                                sleep_fn=clock.advance,
                                tick_sink=ticks.append)
    assert [t["tick"] for t in ticks] == [0, 1, 2]
    assert ticks[-1]["spans"][-1][0] == "record"


@pytest.mark.parametrize("sink", [False, True], ids=["bare", "recorded"])
def test_checked_is_read_only_for_a_record(params, monkeypatch, sink):
    """The iteration's pool check verifies what changed since the last
    one, and its record says how much: `checked`, [pages, slots]. A
    bare run reads it nowhere."""
    from mpi_cuda_cnn_tpu.serve.scheduler import _SchedulerBase

    reads = []

    def get(self):
        reads.append(1)
        return self.__dict__["_checked"]

    def put(self, value):
        self.__dict__["_checked"] = value

    monkeypatch.setattr(_SchedulerBase, "checked", property(get, put),
                        raising=False)
    ticks = []
    clock = TickingClock()
    res = make_engine(params).run(requests(), time_fn=clock,
                                  sleep_fn=clock.advance,
                                  tick_sink=ticks.append if sink else None)
    assert all(r.status == "finished" for r in res.requests)
    assert len(reads) == len(ticks) == (len(ticks) if sink else 0)
    if sink:
        got = [t["checked"] for t in ticks]
        # Two slots over a pool of 15 pages: the first iteration admits
        # both requests (two pages each), and no check is a whole scan.
        assert got[0] == [4, 2]
        assert all(p < 15 and s <= 2 for p, s in got)
        assert [0, 0] in got


def test_a_pool_field_written_behind_the_mutators_fails_the_run_at_its_end(
        params, monkeypatch):
    """The per-iteration check sees what a mutator touched; a field
    written behind all of them is the run-end full scan's to catch,
    after every iteration's record was delivered."""
    from mpi_cuda_cnn_tpu.serve.core import ServeCore

    real = ServeCore.settle

    def settle(self, out):
        if self.steps == 2:             # a page no request ever takes
            self.sched.pool._readers[self.sched.pool.num_pages - 1] = [0]
        return real(self, out)

    clean, want = serve(make_engine(params), requests(), TickingClock())
    monkeypatch.setattr(ServeCore, "settle", settle)
    ticks = []
    clock = TickingClock()
    with pytest.raises(AssertionError, match="readers on unowned page"):
        make_engine(params).run(requests(), time_fn=clock,
                                sleep_fn=clock.advance,
                                tick_sink=ticks.append)
    assert [t["tick"] for t in ticks] == [t["tick"] for t in want]


def test_speculative_round_has_the_ticks_three_spans(params):
    engine = make_engine(params, spec="lookup", spec_k=3)
    res, ticks = serve(engine, requests(), TickingClock(), spec=True)
    assert all(r.status == "finished" for r in res.requests)
    rounds = [t for t in ticks if t.get("spec")]
    assert rounds
    for t in rounds:
        names = " ".join(n for n, _, _ in t["spans"])
        assert "tick.build tick.dispatch tick.wait emit" in names
    assert ticks[-1]["compiled"] == 2     # prefill and the verify block


def test_phases_reach_the_profilers_host_track(tmp_path):
    """Each span lies under a TraceAnnotation `<prefix>/<phase>` whose
    argument is the iteration's index — the same span, on the clock
    the device trace is on; a part under `<prefix>/<phase>/<part>`
    nested inside its phase's, a generation-2 collection under
    `serve.gc/2`."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rec = PhaseSpans("serve.iter")
        rec.watch("serve")
        rec.begin(7, "schedule")
        with rec.part("check"):
            gc.collect()
        rec.enter("tick.wait")
        got = rec.fetch(jax.numpy.arange(3), np.asarray)
        spans = rec.end()
        extras = rec.extras()
        rec.unwatch()
    finally:
        jax.profiler.stop_trace()
    assert [n for n, _, _ in spans] == ["schedule", "tick.wait"]
    assert spans[0][2] == spans[1][1]
    assert list(got) == [0, 1, 2]
    assert [n for n, _, _ in extras["parts"]] == ["schedule/check",
                                                  "tick.wait/fetch"]
    assert [s[0] for s in extras["stops"] if s[0] == "gc"] == ["gc"]
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    events = {e.name: e for p in data.planes for line in p.lines
              for e in line.events if e.name.startswith("serve.")}
    assert {n: dict(e.stats) for n, e in events.items()} == {
        "serve.iter/schedule": {"tick": 7},
        "serve.iter/tick.wait": {"tick": 7},
        "serve.iter/schedule/check": {"tick": 7},
        "serve.iter/tick.wait/fetch": {"tick": 7},
        "serve.gc/2": {}}

    def inside(inner, outer):
        i, o = events[inner], events[outer]
        return (o.start_ns <= i.start_ns and i.start_ns + i.duration_ns
                <= o.start_ns + o.duration_ns)

    assert inside("serve.iter/schedule/check", "serve.iter/schedule")
    assert inside("serve.gc/2", "serve.iter/schedule/check")
    assert inside("serve.iter/tick.wait/fetch", "serve.iter/tick.wait")


# -- the benchmark's readers ----------------------------------------------

def reader(name: str):
    """benchmarks/layer_metrics/<name>.py, loaded by path as run.py does."""
    return load_by_path(ROOT / "benchmarks" / "layer_metrics" / f"{name}.py")


def iteration(t0: float, *, tick_ms: float = 20.0, host_ms: float = 1.0,
              chunk: bool = True, decode: bool = True, compiled: int = 0,
              fetch_ms: float = 0.5, check_ms: float = 0.05,
              gc_s=(0.0, 0.0, 0.0), stops=()) -> dict:
    """One hand-written record starting at t0: schedule 0.2 ms, build
    `host_ms` in all, dispatches 0.1 ms each, a wait of `tick_ms`,
    emit + bookkeep 0.3 ms, record 0.1 ms. Parts: a quarter of each
    build its tables and a quarter its puts, the wait's last `fetch_ms`,
    bookkeep's first `check_ms`; `stops` as (kind, start, end, what)
    with start and end in ms after t0."""
    at, spans, parts = t0, [], []

    def add(name, ms):
        nonlocal at
        spans.append([name, round(at, 6), round(at + ms / 1e3, 6)])
        at += ms / 1e3

    def part(name, since_ms, ms):
        a = spans[-1][1] + since_ms / 1e3
        parts.append([f"{spans[-1][0]}/{name}", round(a, 6),
                      round(a + ms / 1e3, 6)])

    add("schedule", 0.2)
    add("prefill.build", host_ms / 2)
    part("tables", 0.0, host_ms / 8)
    part("puts", host_ms / 8, host_ms / 8)
    if chunk:
        add("prefill.dispatch", 0.1)
    add("grow", 0.1)
    if decode:
        add("tick.build", host_ms / 2)
        part("tables", 0.0, host_ms / 8)
        part("puts", host_ms / 8, host_ms / 8)
        add("tick.dispatch", 0.1)
        add("tick.wait", tick_ms)
        part("fetch", tick_ms - fetch_ms, fetch_ms)
        add("emit", 0.1)
    add("bookkeep", 0.2)
    part("check", 0.0, check_ms)
    add("record", 0.1)
    return {"spans": spans, "compiled": compiled, "now": spans[-1][1],
            "parts": parts, "gc_s": list(gc_s),
            "stops": [[k, round(t0 + a / 1e3, 6), round(t0 + b / 1e3, 6), w]
                      for k, a, b, w in stops]}


def window(special=None, first_tick: int = 0) -> list[dict]:
    """Ten iterations, 0.2 ms of sink between records; `special` maps
    an index to iteration() keywords."""
    ticks, at = [], 0.0
    for i in range(10):
        ticks.append(iteration(at, **(special or {}).get(i, {})))
        ticks[-1]["tick"] = first_tick + i
        at = ticks[-1]["spans"][-1][2] + 0.0002
    return ticks


class FakeTrace:
    def __init__(self, window_s, busy_s):
        self.window_s, self.busy_s = window_s, busy_s


def ctx_of(ticks, **kw):
    end = ticks[-1]["spans"][-1][2] if "spans" in ticks[-1] else 1.0
    return {"ticks": ticks, "first_traced": 4, "window_s": end, **kw}


QUIET = window()
# An iteration of QUIET: 0.2 + 1.0 + 0.2 + 0.1 = 1.5 ms before the
# tick's dispatch ends, a 20 ms wait, 0.4 ms after it, 0.2 ms of sink.
# Exposed, from a wait's end to the next PREFILL dispatch's start: 0.4
# + 0.2 + 0.2 + 0.5 = 1.3 ms (the call itself runs under the device it
# launches, and the tick's dispatch queues behind the chunk).
READINGS = [
    ("host_exposed_ms", QUIET, {}, 1.3),
    # without a chunk the stretch runs on to the tick's dispatch:
    # 1.3 + grow 0.1 + the tick's build 0.5
    ("host_exposed_ms", window({i: {"chunk": False} for i in range(10)}),
     {}, 1.9),
    ("host_schedule_ms", QUIET, {}, 0.2),
    ("host_build_ms", QUIET, {}, 1.3),        # builds 1.0, dispatches 0.2, grow 0.1
    ("host_bookkeep_ms", QUIET, {}, 0.3),
    # ten waits of 20 ms over ten iterations of 22.1 ms less the last sink
    ("device_wait_share", QUIET, {}, 100 * 200 / (221 - 0.2)),
    ("host_stall_ms", QUIET, {}, 0.0),
    # iteration 6's host stood still for 50 ms while building: its own
    # time is 52.1 ms against a median of 2.1 (the first: 1.9, no sink)
    ("host_stall_ms", window({6: {"host_ms": 51.0}}), {}, 52.1 - 3 * 2.1),
    # the profiler's start (the sink before record `first_traced`) is
    # nobody's stall: 300 ms there read as nothing
    ("host_stall_ms", [dict(t, spans=[[n, a + (0.3 if i >= 4 else 0.0),
                                       b + (0.3 if i >= 4 else 0.0)]
                                      for n, a, b in t["spans"]])
                       for i, t in enumerate(QUIET)], {}, 0.0),
    ("device_stall_ms", QUIET, {}, 0.0),
    # iteration 3 waited 100 ms where its like wait 20: 100 - 60; one
    # without a chunk (another program mix) is judged by its own median
    ("device_stall_ms", window({3: {"tick_ms": 100.0},
                                5: {"chunk": False, "tick_ms": 70.0}}),
     {}, 40.0),
    # traced records 4..9: five stretches of 1.3 ms begin and end there;
    # a slice that was idle 5 x 1.9 ms leaves 0.6 ms each unexplained
    ("idle_unexplained_ms", QUIET,
     {"trace": FakeTrace(0.1300, 0.1300 - 5 * 0.0019)}, 0.6),
    ("programs_compiled_in_window", QUIET, {}, 0.0),
    ("programs_compiled_in_window", window({i: {"compiled": 1}
                                            for i in range(7, 10)}), {}, 1.0),
    # a compile in the window's FIRST iteration (a prefill bucket the
    # warm-up missed) counts: the count starts before the first dispatch
    ("programs_compiled_in_window", window({i: {"compiled": 1}
                                            for i in range(10)}), {}, 1.0),
    # records that begin mid-run: the first is all there is to go by
    ("programs_compiled_in_window",
     window({i: {"compiled": 1 + (i >= 5)} for i in range(10)},
            first_tick=40), {}, 1.0),
]

# ISSUE 36's readers of the parts and stops.
PART_READINGS = [
    ("host_fetch_ms", QUIET, {}, 0.5),
    # six iterations that read no tokens (a mid-prompt chunk alone):
    # not among those the median is over
    ("host_fetch_ms", window({i: {"decode": False} for i in range(6)}),
     {}, 0.5),
    ("host_fetch_ms", window({i: {"fetch_ms": 2.5} for i in range(6)}),
     {}, 2.5),
    ("host_check_ms", QUIET, {}, 0.05),
    ("host_check_ms", window({i: {"check_ms": 0.15} for i in range(3)}),
     {}, 0.05),
    # two builds of host_ms / 8 each: 2 x 0.125
    ("host_tables_ms", QUIET, {}, 0.25),
    ("host_tables_ms", window({i: {"decode": False} for i in range(6)}),
     {}, 0.125),
    ("host_puts_ms", QUIET, {}, 0.25),
    ("host_puts_ms", window({i: {"host_ms": 4.0} for i in range(6)}),
     {}, 1.0),
    ("host_gc_ms", QUIET, {}, 0.0),
    # every generation, every record: 1 + 2 + 150 ms
    ("host_gc_ms", window({3: {"gc_s": (0.001, 0.002, 0.0)},
                           7: {"gc_s": (0.0, 0.0, 0.15)}}), {}, 153.0),
    ("compile_ms_in_window", QUIET, {}, 0.0),
    # a trace, one nested in it (counted once), a compile after it:
    # 10 + 20 ms; a collection is no compile
    ("compile_ms_in_window",
     window({5: {"stops": [("compile", 1, 11, "jaxpr_trace:f"),
                           ("compile", 3, 5, "jaxpr_trace:g"),
                           ("compile", 11, 31, "backend_compile:jit_f"),
                           ("gc", 40, 90, 2)]}}), {}, 30.0),
    # one that began before the window's first span counts from there
    ("compile_ms_in_window",
     window({0: {"stops": [("compile", -5, 2, "backend_compile:jit_h")]}}),
     {}, 2.0),
]


@pytest.mark.parametrize(
    "name,ticks,extra,want", READINGS + PART_READINGS,
    ids=[f"{r[0]}-{i}" for i, r in enumerate(READINGS + PART_READINGS)])
def test_reader_on_hand_written_records(name, ticks, extra, want):
    got = reader(name).read(ctx_of(ticks, **extra))
    assert got == pytest.approx(want, abs=1e-6)


NEW_FIELDS = ("parts", "gc_s", "stops")


@pytest.mark.parametrize(
    "name,ticks,extra,want", READINGS,
    ids=[f"{r[0]}-{i}" for i, r in enumerate(READINGS)])
def test_span_readers_read_the_same_without_the_parts(name, ticks, extra,
                                                      want):
    """The nine readers of the spans take no notice of the new fields:
    the same value from the records of an engine from before them."""
    old = [{k: v for k, v in t.items() if k not in NEW_FIELDS}
           for t in ticks]
    assert reader(name).read(ctx_of(old, **extra)) == pytest.approx(
        want, abs=1e-6)


@pytest.mark.parametrize("name", sorted({r[0] for r in PART_READINGS}))
def test_part_reader_reads_nothing_from_records_without_the_fields(name):
    """The parent commit under this benchmark: spans and no parts,
    `gc_s` or `stops` — None, and no exception."""
    old = [{k: v for k, v in t.items() if k not in NEW_FIELDS}
           for t in QUIET]
    assert reader(name).read(ctx_of(old)) is None
    # one record without them (a mixed stream) is as good as none
    mixed = [dict(t) for t in QUIET]
    for k in NEW_FIELDS:
        del mixed[3][k]
    assert reader(name).read(ctx_of(mixed)) is None


@pytest.mark.parametrize(
    "name", sorted({r[0] for r in READINGS + PART_READINGS}))
def test_reader_reads_nothing_from_records_without_spans(name):
    """An engine from before the spans (the parent commit under this
    benchmark): None, never a made-up value, and no exception."""
    old = [{k: v for k, v in t.items() if k not in ("spans", "compiled")}
           for t in QUIET]
    ctx = ctx_of(old, trace=FakeTrace(0.13, 0.12))
    assert reader(name).read(ctx) is None
    assert reader(name).read({**ctx, "ticks": []}) is None
