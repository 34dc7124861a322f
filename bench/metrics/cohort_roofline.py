"""Cohort programs: the least time the chip could take for the real client
steps (the larger of their operations over peak FLOP/s and their bytes
over peak HBM bandwidth, from the model family's step_flops and
step_bytes) over the device time of the cohort programs (jit_train_one)
in the trace, in %."""

PROGRAM = "jit_train_one"


def read(ctx):
    t = ctx["trace"]["modules"].get(PROGRAM)
    if not t or not ctx["real_flops"]:
        return None
    p = ctx["peaks"]
    least = max(ctx["real_flops"] / p["flops_bf16"],
                ctx["real_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / t
