"""Share of the chips' peak that the learner steps of the traced window
need: actor forward on T*B frames plus learner forward and backward on
(T+1)*B frames per step (chip.flops.impala_step_flops), over the traced
window, the chips and their peak, in percent."""

from chip.flops import impala_step_flops

PROGRAM = "jit_train_step"


def read(ctx):
    prog = ctx["trace"]["programs"].get(PROGRAM)
    if not prog or not prog["calls"]:
        return None
    c = ctx["counters"]
    step = impala_step_flops(tuple(c["obs_shape"]), tuple(c["channels"]),
                             c["fc"], c["num_actions"], c["unroll_length"],
                             c["batch"])
    window = ctx["trace"]["window_s"]
    return 100.0 * prog["calls"] * step / (
        window * ctx["chips"] * ctx["peaks"]["flops"])
