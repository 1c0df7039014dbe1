"""fused_matmul_roofline: least time the linears' work needs at the chip's
peaks over the fused quantize->matmul kernel's summed device time.

Each kernel call is read from the trace: its op's HLO text gives the
rows the step handed it and the weight's shape (``work.traced_matmul``),
so a ragged or resized dispatch is followed without an edit.  Its work is
``work.matmul``; per call the larger of compute and memory time.  The
kernel's ops are named after its wrapper, ``mxsf_fused_matmul_pallas``
(the program gives its ``pallas_call`` no ``name=``).  Layer: the linears
(``core/mx_dot.py`` -> ``kernels/mxsf_fused_matmul.py``).
"""
import trace_reduce as tr
import work

KERNEL = "mxsf_fused_matmul_pallas"


def read(run):
    secs = floor = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for name, a, b in tr.kernel_ops(run.ops, KERNEL, run.t0_ns, run.t1_ns):
        shape = work.traced_matmul(name)
        if shape is None:
            raise ValueError(f"no matmul shape in trace op {name[:200]!r}")
        t, bound = work.least_time(*work.matmul(*shape), run.peaks)
        secs += (b - a) * 1e-9
        floor += t
        by_bound[bound] += t
    if secs <= 0:
        return None
    return {"value": 100.0 * floor / secs,
            "bound": max(by_bound, key=by_bound.get)}
