"""The one serving iteration (serve/core.py's ServeCore), driven
directly: a recording stand-in for the compute, a recording stand-in
for the phase recorder and a clock that moves on every read, so the
order of calls, of span boundaries and of stamps is what is asserted —
no model, no jax."""

import numpy as np

from mpi_cuda_cnn_tpu.serve.core import ServeCore, build_scheduler
from mpi_cuda_cnn_tpu.serve.scheduler import Request

GEOM = dict(slots=2, num_pages=9, page_size=4, max_len=32)


class Clock:
    """Moves 1 ms on every read: no two stamps coincide."""

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        self.now = round(self.now + 0.001, 6)
        return self.now


class Spans:
    """obs.trace.PhaseSpans' `enter`, recorded: a boundary the loop
    read the clock for carries its stamp, the others None. Its `fetch`
    reads and records nothing."""

    def __init__(self, log):
        self.log = log

    def enter(self, phase, t=None):
        self.log.append(("enter", phase, t))

    def fetch(self, x, read):
        """The completing chunk's read: no device, nothing to wait for."""
        return read(x)


class Compute:
    """Records each call; like the engine's device path it tells the
    recorder where a dispatch and the wait for its tokens begin. Token
    j of request rid is 100 * rid + j."""

    def __init__(self, log, spans, chunk=8):
        self.log, self.spans, self.chunk = log, spans, chunk

    def _tok(self, req):
        return 100 * req.rid + len(req.out)

    def prefill_chunk(self, slot):
        n = min(self.chunk, slot.target - slot.cached)
        self.spans.enter("prefill.dispatch")
        self.log.append(("prefill_chunk", slot.req.rid, n))
        return n, self._tok(slot.req)

    def decode(self, dslots):
        self.spans.enter("tick.dispatch")
        self.log.append(("decode", [s.req.rid for s in dslots]))
        self.spans.enter("tick.wait")
        return {s.idx: self._tok(s.req) for s in dslots}

    def copy_page(self, src, dst):
        self.log.append(("copy_page", src, dst))


def request(rid, n_prompt=6, new=3, arrival=0.0):
    return Request(rid=rid, prompt=np.arange(n_prompt, dtype=np.int32),
                   max_new_tokens=new, arrival=arrival)


def make_core(log, mode="continuous"):
    clock, spans = Clock(), Spans(log)
    core = ServeCore(Compute(log, spans), build_scheduler(mode=mode, **GEOM),
                     clock=clock, spans=spans,
                     on_emit=lambda req, tok, now: log.append(
                         ("emit", req.rid, tok, now)))
    return core, clock


def test_iteration_with_a_completing_chunk_and_a_tick_runs_in_order():
    log = []
    core, clock = make_core(log)
    core.sched.submit([request(0), request(1)])
    out = core.step(now=clock())             # judged at 0.001
    # Request 0's one chunk completes: its first token is stamped by
    # the read AFTER the wait (0.002); `grow` opens at the next read
    # (0.003); the tick's tokens are stamped by the read after it.
    assert log == [
        ("enter", "prefill.build", None),
        ("enter", "prefill.dispatch", None),
        ("prefill_chunk", 0, 6),
        ("enter", "prefill.wait", None),
        ("enter", "emit", 0.002),
        ("emit", 0, 0, 0.002),
        ("enter", "grow", 0.003),
        ("enter", "tick.build", None),
        ("enter", "tick.dispatch", None),
        ("decode", [0]),
        ("enter", "tick.wait", None),
        ("enter", "emit", 0.004),
        ("emit", 0, 1, 0.004),
    ]
    assert out.admitted == [[0, 0], [1, 1]]
    assert out.prefill == [0, 0, 6, "emit"] and out.decoded == [[0, 0]]
    assert out.emitted == 2 and out.progressed and out.moved
    assert core.prefill_chunks == core.decode_ticks == core.steps == 1
    first = core.sched.slots[0].req
    assert first.first_token_at == 0.002 and first.out == [0, 1]
    # The bookkeeping half: drained logs, no terminal tail yet, a digest.
    assert out.preempted_pairs == [] and out.prefix_tick is None
    assert out.new_fin == out.new_drop == []
    assert isinstance(out.state_crc, int)
    fields = core.tick_fields(out)
    assert fields["running"] == 2 and fields["prefill"] == out.prefill
    assert "spec" not in fields and "prefix" not in fields

    # Next iteration: request 1's chunk completes, then BOTH decode;
    # request 0 reaches its budget and is finished at the tick's stamp.
    del log[:]
    out = core.step(now=clock())
    assert [e for e in log if e[0] in ("prefill_chunk", "decode")] == [
        ("prefill_chunk", 1, 6), ("decode", [0, 1])]
    assert out.emitted == 3
    assert [r.rid for r in out.new_fin] == [0]
    tick_stamp = [e for e in log if e[:2] == ("enter", "emit")][-1][2]
    assert out.new_fin[0].finished_at == tick_stamp
    assert core.tick_fields(out)["finished"] == [0]


def test_a_step_with_nothing_to_do_enters_no_tick_phase():
    log = []
    core, clock = make_core(log)
    core.sched.submit([request(0, arrival=5.0)])
    out = core.step(now=clock())
    assert [e[1] for e in log] == ["prefill.build", "grow"]
    assert not out.progressed and not out.moved
    assert out.admitted == [] and out.prefill is None and out.decoded == []


def test_the_sweep_is_the_drivers_to_ask_for():
    log = []
    core, clock = make_core(log)
    req = request(0, arrival=5.0)
    core.sched.submit([req])
    req.cancel()
    out = core.step(now=clock(), sweep=False)
    assert out.swept == () and req.status != "cancelled"
    out = core.step(now=clock(), sweep=True)
    assert [r.rid for r in out.swept] == [0] and req.status == "cancelled"
    assert [r.rid for r in out.new_drop] == [0]
    assert out.moved and not out.progressed
    assert core.tick_fields(out)["aborted"] == [[0, "cancelled"]]


def test_the_static_scheduler_answers_for_its_own_batch():
    """A request done at its first token keeps its slot under static
    batching until the whole batch drains, and the drain is stamped by
    a clock read of its own; continuous batching releases at once."""
    for mode, held in (("static", True), ("continuous", False)):
        log = []
        core, clock = make_core(log, mode=mode)
        assert core.sched.release_at_once is (not held)
        core.sched.submit([request(0, new=1), request(1, new=2)])
        out = core.step(now=clock())
        assert out.prefill[-1] == "emit"
        assert (not core.sched.slots[0].free) is held
        assert [r.rid for r in out.new_fin] == ([] if held else [0])
        while core.unfinished:
            out = core.step(now=clock())
        assert {r.rid for r in core.sched.finished} == {0, 1}
        if held:
            # Both left together, at the drain's own stamp.
            assert [r.rid for r in out.new_fin] == [0, 1]
            assert len({r.finished_at for r in out.new_fin}) == 1
