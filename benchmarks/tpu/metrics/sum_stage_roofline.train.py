"""Share of its roofline that the Sum stage reaches in training: the sum
over every Sum-stage pass of the window of max(FLOPs / peak FLOP/s,
bytes / peak HBM bandwidth), counted from live edges and widths
(costs/<model>.py), over the kernels' device time in the trace."""
import trace_reduce

# the jitted wrappers of repro/kernels/ops.py name the kernels' custom
# calls in the HLO (e.g. ``jvp_jit__edge_softmax_planned__``)
KERNELS = (r"_(edge_softmax|edge_softmax_bwd|segment_reduce|segment_sum_bwd"
           r"|segment_max_bwd)_planned.*tpu_custom_call")


def read(ctx):
    s, d = ctx["trace"], ctx["driver"]
    if not s or not hasattr(d, "work"):
        return None
    t = trace_reduce.op_time_s(s, KERNELS)
    if t <= 0:
        return None
    pk, model, fdim = ctx["peaks"], ctx["config"]["model"], d.g["x"].shape[1]
    ideal = 0.0
    for w in d.work():
        for fl, by in ctx["costs"].sum_stage_passes(w, model, fdim, True):
            ideal += max(fl / pk["flops"], by / pk["hbm_bytes_per_s"])
    return 100.0 * ideal / ctx["chips"] / t
