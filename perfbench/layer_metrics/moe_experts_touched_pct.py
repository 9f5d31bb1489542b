"""Share of a layer's experts that received a token in a decode step:
median over the window's plain decode steps of the step record's
``moe_experts_touched`` over layers x experts. The expert layer reads
the weights of the experts it touches and no others, so this is the
share of the expert weights a step has to read."""


def read(run):
    from perfbench import moe

    return moe.median_over_decode_steps(
        run, lambda s, sh: 100.0 * s["moe_experts_touched"]
        / (sh[0] * sh[1]))
