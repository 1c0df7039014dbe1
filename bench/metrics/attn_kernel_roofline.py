"""attn_kernel_roofline: least time the cached attention's work needs at
the chip's peaks over the packed-KV attention kernel's summed device time.

Work per dispatch and layer (``attn_rows`` of the configuration's
architecture module; for a dense decoder, ``work.attn_rows``): the codes and
scales of each slot's live positions read once per kv head, queries and
outputs, and 4 * dh FLOPs per (query head, visible key) pair, counted
causally within a prefill chunk.  Decode and prefill dispatches are separate
kernel calls, each bounded by the larger of compute and memory time.  The
kernel's ops are named after its wrapper, ``_flash_attention_jit``.  Layer:
attention (``kernels/mxsf_attention.py``).
"""
import trace_reduce as tr
import work

KERNEL = "_flash_attention_jit"


def read(run):
    secs, count = tr.kernel_time(run.ops, KERNEL, run.t0_ns, run.t1_ns)
    if count == 0 or secs <= 0:
        return None
    s = run.sizes
    floor = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for t in run.ticks:
        for mask in (t.prefill, ~t.prefill):
            f, b = run.arch.attn_rows(s, t.starts[mask], t.rows[mask])
            if f == 0:
                continue
            least, bound = work.least_time(f, b, run.peaks)
            floor += s["num_hidden_layers"] * least
            by_bound[bound] += least
    if floor <= 0:
        return None
    return {"value": 100.0 * floor / secs,
            "bound": max(by_bound, key=by_bound.get)}
