"""Device time of the Sum-stage Pallas kernels per training step, from
the trace."""
import trace_reduce

# the jitted wrappers of repro/kernels/ops.py name the kernels' custom
# calls in the HLO (e.g. ``jvp_jit__edge_softmax_planned__``)
KERNELS = (r"_(edge_softmax|edge_softmax_bwd|segment_reduce|segment_sum_bwd"
           r"|segment_max_bwd)_planned.*tpu_custom_call")


def read(ctx):
    s, d = ctx["trace"], ctx["driver"]
    if not s:
        return None
    t = trace_reduce.op_time_s(s, KERNELS)
    if t <= 0:
        return None
    return 1e3 * t / d.n_steps
