"""attention_roofline: least time the cached attention's work needs at the
chip's peaks over the device time of the ops under the program's
``attention`` scope.

The work is ``attn_kernel_roofline``'s (``attn_rows`` of the
configuration's architecture module; for a dense decoder, live positions
read once per kv head, causal FLOPs; decode and prefill dispatches each
bounded by the larger of compute and memory time).  The time is the union
of the ops whose scope path runs through ``jax.named_scope("attention")``
(``models/blocks.py``): the cache read through scores, softmax and PV,
whichever path computes them, the packed-KV kernel or jnp ops.  Layer:
attention.
"""
import program_trace as pt
import work

SCOPE = "attention"


def read(run):
    trace = pt.load()
    if trace is None:
        return None
    secs = pt.scope_time(trace.ops, SCOPE, run.t0_ns, run.t1_ns)
    if secs <= 0:
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
