"""One transfer a dispatch: every program of the serving engine takes
its host-made inputs — tokens, positions, flags, every layer group's
block table, the linear layers' state rows — as ONE int32 array, put on
the device once (`serve/engine.Packed`), and slices them out inside.

Two properties, at every cache view the engine builds: K/V pools in
f32, bf16 and int8, the latent pool, a windowed group beside the global
one, the linear layers' slot states, several residual streams, and the
speculative verify tick:

- a run makes exactly one host->device transfer a dispatched program,
  and nothing else crosses to the device (a transfer guard refuses any
  other, `jnp.asarray` and a `jnp.int32` scalar among them); the tick
  record's `puts` says so;
- the inputs the program unpacks are those the engine built as
  separate arrays before packing: the same tokens, positions and flags,
  block tables (a slot whose pages fill the table's whole width, a
  windowed group's scratch entries, the tick's dead rows) and state
  rows.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM  # noqa: E402
from mpi_cuda_cnn_tpu.serve.engine import Packed, PagedEngine  # noqa: E402
from mpi_cuda_cnn_tpu.serve.paged_cache import SlotStates  # noqa: E402
from mpi_cuda_cnn_tpu.serve.scheduler import Request, Slot  # noqa: E402

BENCH = ROOT / "benchmarks"
SEED = 2**31 + 43
SLOTS, PAGE, CHUNK, MAX_LEN = 3, 4, 8, 64


def kv(dtype):
    model = TransformerLM(vocab=64, dim=32, heads=4, kv_heads=2, depth=2,
                          max_seq=MAX_LEN, pos="rope")
    return model, model.init(jax.random.key(0)), {"cache_dtype": dtype}


def family(name, tiny, **over):
    fam = run.load_family(BENCH / "families" / name)
    path = BENCH / "tests" / tiny / "configs"
    cfg = {**json.loads(next(path.glob("*.json")).read_text()),
           "weights_dtype": "float32", "cache_dtype": "float32", **over}
    dm = fam.weights.dims(cfg)
    return (fam.build.model_of(dm), fam.build.serving_params(dm, SEED, cfg),
            {"cache_dtype": "float32"})


LAYOUTS = {
    "kv-float32": lambda: kv("float32"),
    "kv-bfloat16": lambda: kv("bfloat16"),
    "kv-int8": lambda: kv("int8"),
    "latent": lambda: family("mla_moe", "tiny_mla"),
    "window": lambda: family("window_moe", "tiny_window",
                             sliding_window_size=8,
                             max_position_embeddings=MAX_LEN,
                             vocab_size=96),
    "states": lambda: family("sparse_linear", "tiny_sparse_linear",
                             vocab_size=96),
    "streams": lambda: family("hc_mla_moe", "tiny_hc"),
    "spec": lambda: (*kv("float32")[:2],
                     {"spec": "lookup", "spec_k": 4}),
}


@pytest.fixture(scope="module", params=sorted(LAYOUTS))
def served(request):
    model, params, kw = LAYOUTS[request.param]()
    eng = PagedEngine(model, params, slots=SLOTS, page_size=PAGE,
                      num_pages=SLOTS * (MAX_LEN // PAGE) + 1,
                      prefill_chunk=CHUNK, max_len=MAX_LEN, **kw)
    return request.param, model, eng


def requests(vocab):
    rng = np.random.default_rng(43)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(((9, 6), (20, 4), (5, 8), (13, 5)))]


def test_packed_fields_lie_end_to_end():
    packed = Packed(("a", (2, 3)), ("s", ()), ("b", (4,)))
    assert packed.size == 11
    buf, x = packed.host()
    x["a"][1] = 7
    x["s"][...] = 5
    x["b"][:] = np.arange(4)
    assert buf.tolist() == [0, 0, 0, 7, 7, 7, 5, 0, 1, 2, 3]
    on_device = packed.split(jax.device_put(buf))
    assert {k: np.asarray(v).tolist() for k, v in on_device.items()} == {
        "a": [[0, 0, 0], [7, 7, 7]], "s": 5, "b": [0, 1, 2, 3]}


def test_every_dispatch_is_one_transfer(served, monkeypatch):
    """Each program the run dispatches costs one host->device transfer,
    one packed int32 array, and the run makes no other: the tick
    record's `puts` equals its count of dispatches, at every view."""
    name, model, eng = served
    spec = name == "spec"
    eng.run(requests(model.vocab), spec=spec)      # compiles every form
    made = []
    real = jax.device_put

    def put(x, *a, **kw):
        made.append(x)
        with jax.transfer_guard_host_to_device("allow"):
            return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", put)
    ticks = []
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        res = eng.run(requests(model.vocab), spec=spec,
                      tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 4}
    dispatched = [sum(n.endswith(".dispatch") for n, _, _ in t["spans"])
                  for t in ticks]
    assert [t["puts"] for t in ticks] == dispatched
    assert len(made) == sum(dispatched)
    assert all(isinstance(m, np.ndarray) and m.dtype == np.int32
               and m.ndim == 1 for m in made)
    assert {m.size for m in made} == {
        eng._layouts[p].size
        for p in ("prefill", "spec" if spec else "tick")}
    phases = {n for t in ticks for n, _, _ in t["spans"]}
    assert {"prefill.dispatch", "tick.dispatch"} <= phases


def slot(eng, idx, pages, wpages, cached, **kw):
    req = Request(rid=idx, prompt=np.arange(3, 3 + 2 * CHUNK, dtype=np.int32)
                  % eng.model.vocab, max_new_tokens=4)
    req.out = [11, 12]
    return Slot(idx=idx, req=req, pages=pages, wpages=wpages, cached=cached,
                **kw)


def separate_tables(eng, rows, pairs):
    """The block tables as the engine built them before they were
    packed: one zeroed (rows, table width) array a layer group."""
    groups = [lambda s: s.pages]
    if eng._window is not None:
        groups.append(lambda s: s.wpages)
    tables = []
    for pages_of in groups:
        table = np.zeros((rows, eng._table_width), np.int32)
        for i, s in pairs:
            table[i, : len(pages_of(s))] = pages_of(s)
        tables.append(table)
    return tables


def unpacked(eng, program, fn_name, monkeypatch):
    """Wrap the engine's jitted `program`, for the test, to keep the
    array it is handed; return a function that gives what the program
    unpacks from it (`_unpack`, the code the program runs, run
    eagerly)."""
    kept = []
    real = getattr(eng, fn_name)

    def call(store, params, packed):
        kept.append(packed)
        return real(store, params, packed)

    monkeypatch.setattr(eng, fn_name, call)
    return lambda: eng._unpack(program, (eng._pages, eng._states), kept[-1])


def views(eng, cache):
    """(block tables, state rows or None) of a program's cache view."""
    rows = None
    if eng._states is not None:
        cache, store = cache
        assert isinstance(store, SlotStates)
        rows = np.asarray(store.rows).tolist()
    caches = cache if isinstance(cache, tuple) else (cache,)
    assert [c.window for c in caches] == (
        [0] if eng._window is None else [0, eng._window[0]])
    return [np.asarray(c.block_table) for c in caches], rows


def test_the_program_unpacks_the_inputs_built_apart(served, monkeypatch):
    """What each program slices out of its one array is what the
    engine built as separate arrays: the chunk's tokens and two
    scalars, the tick's (or verify's) tokens, positions and flags with
    its dead rows zero, every group's block table — a slot whose pages
    fill the table's whole width, a windowed group's scratch entries —
    and each batch row's slot for the linear layers' states."""
    name, model, eng = served
    width = eng._table_width
    full = slot(eng, 2, list(range(1, width + 1)),
                [0, 0, 0, *range(1, width - 2)], cached=CHUNK)
    full.target = 2 * CHUNK + 2
    short = slot(eng, 0, [width + 1, width + 2], [0, width + 1], cached=5)

    read = unpacked(eng, "prefill", "_prefill", monkeypatch)
    n, _ = eng.run_prefill_chunk(full)
    x, cache = read()
    ctx = np.concatenate([full.req.prompt, full.req.out])
    toks = np.zeros((1, CHUNK), np.int32)
    toks[0, :n] = ctx[CHUNK: CHUNK + n]
    assert np.asarray(x["toks"]).tolist() == toks.tolist()
    assert (int(x["pos0"]), int(x["n_valid"])) == (CHUNK, n)
    assert np.shape(x["pos0"]) == np.shape(x["n_valid"]) == ()
    tables, rows = views(eng, cache)
    want = separate_tables(eng, 1, [(0, full)])
    assert [t.tolist() for t in tables] == [t.tolist() for t in want]
    assert tables[0][0, -1] == width            # the whole width
    assert rows in (None, [2])

    dslots = [full, short]                      # row 1 rides along dead
    if name == "spec":
        read = unpacked(eng, "spec", "_spec", monkeypatch)
        rounds = [(full, [5, 6, 7], 3), (short, [8, 9], 2)]
        eng.run_spec_tick(rounds)
        x, cache = read()
        toks = np.zeros((SLOTS, eng.spec_k), np.int32)
        valid = np.zeros((SLOTS, eng.spec_k), bool)
        for s, u, w in rounds:
            toks[s.idx, :w] = u
            valid[s.idx, :w] = True
        assert np.asarray(x["toks"]).tolist() == toks.tolist()
        assert (np.asarray(x["valid"]) != 0).tolist() == valid.tolist()
    else:
        read = unpacked(eng, "tick", "_tick", monkeypatch)
        eng.run_decode_tick(dslots)
        x, cache = read()
        assert np.asarray(x["toks"]).tolist() == [12, 0, 12]
        assert (np.asarray(x["live"]) != 0).tolist() == [True, False, True]
    assert np.asarray(x["pos"]).tolist() == [5, 0, CHUNK]
    tables, rows = views(eng, cache)
    want = separate_tables(eng, SLOTS, [(s.idx, s) for s in dslots])
    assert [t.tolist() for t in tables] == [t.tolist() for t in want]
    assert not tables[0][1].any()               # the dead row: scratch
    if eng._window is not None:
        assert tables[1][2, :3].tolist() == [0, 0, 0]
    assert rows in (None, list(range(SLOTS)))
    assert (rows is None) == (eng._states is None)
