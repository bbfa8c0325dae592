"""Checks of the yardstick itself, run by hand (no chip needed):

    JAX_PLATFORMS=cpu python benchmarks/check_benchmark.py

1. reduce_trace.py on testdata/'s small recorded trace: the busy union
   against a second, independent count (a sweep over sorted end
   points); module time by name, the gap list and the top operation
   against the numbers recorded with the trace; the GEMV pattern finds
   the kernel's calls (each tick and chunk makes 5 per layer and 1 for
   the head).
2. traffic/poisson_lognormal.py draws the lengths and arrivals of
   `serve/bench.make_workload(len_dist="lognormal")` for the same seed
   and ranges where a cell gives a range alone; for every cell, the
   lognormal goes through the median and mean its source publishes
   and no request outgrows the deployment's `max_len`; the vocabulary
   is the one the configuration's family gives (`dims(cfg)["vocab"]`).
3. work.py's kernel counts, and every family's own `work.check()`,
   against counts written out by hand at one small shape.
4. BENCHMARK.json: every name has its file, every reader loads, every
   configuration names a family whose four modules load.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import reduce_trace, work  # noqa: E402
from benchmarks.run import (  # noqa: E402
    families_in,
    family_dir,
    load_by_path,
    load_cell,
    load_family,
)

HERE = Path(__file__).resolve().parent


def close(a, b, tol=1e-9):
    return math.isclose(a, b, rel_tol=tol, abs_tol=1e-12)


def check_reducer():
    want = json.loads((HERE / "testdata/tiny-mqa.expected.json").read_text())
    tr = reduce_trace.load(HERE / "testdata/tiny-mqa.xplane.pb.gz")
    ops = tr.chips[0].ops
    # Independent busy time: +1 at every start, -1 at every end.
    points = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops],
                    key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in points:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert close(tr.busy_s, busy, 1e-6), (tr.busy_s, busy)
    assert close(tr.busy_s, want["busy_s"], 1e-6), tr.busy_s
    assert close(tr.window_s, want["window_s"]), tr.window_s
    assert len(ops) == want["device_ops"], len(ops)
    for name, (count, seconds) in want["modules"].items():
        runs = tr.module_durations(name)
        assert len(runs) == count and close(sum(runs), seconds, 1e-6), name
    _, seconds, module, nth = tr.gaps(1)[0]
    assert [module, nth] == want["longest_gap"][1:], (module, nth)
    assert close(seconds, want["longest_gap"][0], 1e-6), seconds
    name, seconds = tr.top_ops(1)[0]
    assert name == want["top_op"][0] and close(seconds, want["top_op"][1], 1e-6)
    gemv = load_by_path(HERE / "layer_metrics/int8_gemv_roofline.py")
    _, calls = tr.op_seconds(gemv.KERNEL)
    runs = want["modules"]["jit_tick"][0] + want["modules"]["jit_prefill"][0]
    assert calls == want["int8_gemv_calls"] == runs * (5 * 3 + 1), calls
    assert reduce_trace.short_name(
        '%tick.244 = f32[64,16384]{1,0:T(8,128)S(1)} custom-call(f32[64,4096]'
        '{1,0:T(8,128)S(1)} %multiply_add_fusion.71, s8[4096,16384]{1,0:T(8,128)'
        '(4,1)} %params__blocks___6___w1___q.1, f32[1,16384]{1,0:T(1,128)S(1)} '
        '%copy-done.352), custom_call_target="tpu_custom_call", operand_layout'
    ) == ("tick f32[64,16384] = tpu_custom_call(f32[64,4096], "
          "s8[4096,16384], f32[1,16384])")
    print("reduce_trace: ok")


def check_traffic():
    from mpi_cuda_cnn_tpu.serve.bench import make_workload

    gen = load_by_path(HERE / "traffic" / "poisson_lognormal.py")
    # make_workload's own shape (no median, mean or sigma given): the
    # same arrivals and lengths, request for request.
    for rate, (pmin, pmax), (omin, omax), vocab in [
            (3.0, (32, 512), (64, 768), 49152),
            (1.25, (32, 1024), (16, 512), 50257),
            (6.0, (8, 96), (4, 48), 512)]:
        p = {"rate_rps": rate, "prompt": {"min": pmin, "max": pmax},
             "out": {"min": omin, "max": omax}}
        mine = gen.draw_sizes(p, seconds=30.0, vocab=vocab)
        theirs = make_workload(
            n=len(mine) + 1, vocab=vocab, prompt_min=pmin, prompt_max=pmax,
            out_min=omin, out_max=omax, rate=rate, seed=gen.DRAW_SEED,
            len_dist="lognormal")
        assert theirs[-1].arrival >= 30.0
        assert [(t, pl, ol) for t, pl, ol in mine] == [
            (r.arrival, r.prompt.size, r.max_new_tokens)
            for r in theirs[:-1]], p
        print(f"traffic at {rate}/s: {len(mine)} requests, as make_workload")
    # Every cell: the lognormal goes through what its source publishes,
    # the lengths keep to the deployment, and a seed repeats itself.
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        _, cfg, wl, _ = load_cell(cell["name"], ROOT / "BENCHMARK.json")
        gen = load_by_path(HERE / "traffic" / f"{wl['generator']}.py")
        family = load_family(family_dir(cfg, HERE))
        p, vocab = wl["params"], family.weights.dims(cfg)["vocab"]
        for length in (p["prompt"], p["out"]):
            mu, sigma = gen.mu_sigma(length)
            if "median" in length:
                assert close(math.exp(mu), length["median"])
            if "mean" in length:
                assert close(math.exp(mu + sigma * sigma / 2), length["mean"])
        sizes = gen.draw_sizes(p, seconds=float(bench["run_seconds"]),
                               vocab=vocab)
        assert max(pl + ol for _, pl, ol in sizes) <= int(cfg["max_len"])
        reqs = gen.generate(p, seed=2**31 + 7, seconds=5.0, vocab=vocab)
        again = gen.generate(p, seed=2**31 + 7, seconds=5.0, vocab=vocab)
        assert all((a[1] == b[1]).all() for a, b in zip(reqs, again))
        pls, ols = [s[1] for s in sizes], [s[2] for s in sizes]
        print(f"traffic {cell['name']}: {len(sizes)} requests in "
              f"{bench['run_seconds']} s; prompts mean {sum(pls) / len(pls):.0f} "
              f"median {sorted(pls)[len(pls) // 2]} max {max(pls)}; answers "
              f"mean {sum(ols) / len(ols):.0f} median "
              f"{sorted(ols)[len(ols) // 2]} max {max(ols)}")


def check_work():
    assert work.int8_gemv_work(4, 8, 16) == (2 * 4 * 8 * 16,
                                             128 + 64 + 128 + 256)
    # One call a forward of each shape: the sum of the calls' own floors.
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}
    assert close(work.int8_gemv_least_seconds(4, [(8, 16, 2)], peaks),
                 2 * max(1024 / 1e3, 576 / 1e2))
    print("work: ok")
    families = {path for bench_dir in (HERE, HERE / "tests/tiny")
                for path in families_in(bench_dir).values()}
    for path in sorted(families):
        load_family(path).work.check()
        print(f"work of family {path.relative_to(ROOT)}: ok")


def check_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    for name in cells:
        cfg = load_cell(name, ROOT / "BENCHMARK.json")[1]
        load_family(family_dir(cfg, HERE))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        reader = load_by_path(HERE / "layer_metrics" / f"{m['name']}.py")
        assert callable(reader.read), m["name"]
        assert m["moves"] in e2e, m
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]].get("workloads", cells), (m["name"], w)
    print(f"files: {len(cells)} cells, {len(bench['per_layer'])} readers")


if __name__ == "__main__":
    check_reducer()
    check_work()
    check_files()
    check_traffic()
