"""Roofline time of the ``int8_matmul`` calls that the traced decode steps
make (M = the slots), from their shapes, over the kernel's device time
inside those steps (layer: kernels).  Weight-only int8 multiplies bf16
activations, so its compute peak is the bf16 one.  Each call is counted
where the trace holds it, so a step cut by the window's edge counts in
part on both sides."""

from harness import ops

# how the device trace names each call of the program's int8_matmul kernel
# (``%int8_matmul.43``); each layer matrix is one call site, run once a layer
KERNEL = "%int8_matmul"


def read(run):
    if run.trace is None or run.cfg["weights"] != "int8":
        return None
    calls = run.traced_calls("decode")
    n, secs = run.trace.op_in("decode_fn", KERNEL)
    if not calls or not n:
        return None
    shapes = ops.int8_calls(run.cfg, calls[0].rows)
    per_call = sum(ops.roofline_s(ops.int8_matmul_ops(*s),
                                  ops.int8_matmul_bytes(*s),
                                  run.peaks["bf16_flops"],
                                  run.peaks["hbm_bytes_per_s"])
                   for s in shapes) / len(shapes)
    return 100.0 * n * per_call / secs
