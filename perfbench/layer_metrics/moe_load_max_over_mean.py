"""How uneven the routing is: median over the window's plain decode
steps of the most tokens one expert of one layer received
(``moe_expert_max``) over the mean an expert received
(``moe_assignments`` / (layers x experts)). 1 is a perfectly even
step; the rows of the fullest expert are what a grouped matmul's
longest group holds."""


def read(run):
    from perfbench import moe

    return moe.median_over_decode_steps(
        run, lambda s, sh: s["moe_expert_max"]
        / (s["moe_assignments"] / (sh[0] * sh[1])))
