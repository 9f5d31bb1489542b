"""Share of a decode step's routed (token, choice) pairs that chose an
identity expert: median over the window's plain decode steps of the step
record's ``moe_zero_pairs`` over its ``moe_pairs`` (live pairs in all,
held here or not). An identity expert returns its token and multiplies
nothing, so this is the share of a token's choices that cost no product
anywhere in the deployment: it holds the mechanism engaged, and a program
that lost the identities, or computed them as products, moves it or the
check. ``None`` for a program whose records lack the fields (a router
without identity experts, or the parent of the PR that brought them)."""


def read(run):
    import statistics

    from perfbench import steplog

    steps = steplog.window_steps(run)
    if steps is None:
        return None
    shares = [100.0 * s["moe_zero_pairs"] / s["moe_pairs"] for s in steps
              if s.get("decodes") and not s.get("prefills")
              and s.get("moe_pairs")]
    return statistics.median(shares) if shares else None
