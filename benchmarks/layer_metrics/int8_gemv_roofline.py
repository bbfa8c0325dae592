"""int8_gemv_roofline (%) — kernels; moves tokens_per_s.

The least time the chip could take for every int8 GEMV call of the
traced slice (work.py: per call the larger of operations over the bf16
peak and bytes over the HBM peak; at these row counts the bytes bound
every call), over the summed device time of the kernel's custom-call
events. The calls are counted from the module runs: each `tick` run
makes one forward's calls (the family's `work.matmul_shapes`) at
`slots` rows, each `prefill` run at `prefill_chunk` rows. Nothing to
read where the configuration's weights are not int8.
"""

from benchmarks import work

# y f32[rows,dout] = tpu_custom_call(x f32[rows,din], q s8[din,dout],
# s f32[1,dout]): the one Pallas call with exactly these three operands.
KERNEL = (r"^\S+ f32\[\d+,\d+\] = tpu_custom_call\(f32\[\d+,\d+\], "
          r"s8\[\d+,\d+\], f32\[1,\d+\]\)$")


def read(ctx):
    if ctx["config"]["weights_dtype"] != "int8":
        return None
    trace = ctx["trace"]
    spent, calls = trace.op_seconds(KERNEL)
    if not calls:
        return None
    shapes = ctx["family"].work.matmul_shapes(ctx["dims"])
    least = 0.0
    for module, rows in (("jit_tick", ctx["slots"]),
                         ("jit_prefill", ctx["prefill_chunk"])):
        runs = len(trace.module_durations(module)) / len(trace.chips)
        least += runs * work.int8_gemv_least_seconds(
            rows, shapes, ctx["peaks"])
    return 100.0 * least / spent
