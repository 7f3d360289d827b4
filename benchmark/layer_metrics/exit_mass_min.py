"""The smallest exit's share of the loss over the window's steps, in percent:
the program's counters ``exit.mass`` (each exit's summed probability over the
tokens) over ``exit.tokens``. At the gate's initial balance the four exits hold
1/2, 1/4, 1/8, 1/8; at a constant 3e-4 from the seed's weights the gate leaves
that balance inside ten steps (AdamW moves each of its 2048 weights by the
rate a step, the logit by about half a unit), and over a window of twenty
steps the smallest exit has read 0.5 to 3.4 % by seed (PERF.md section 6, PR
32). Every exit is computed whatever its weight: the number says how the loss
was shared, not what the step cost. Layer: model step."""


def read(ctx):
    counters = ctx["counters"] or {}
    mass, tokens = counters.get("exit.mass"), counters.get("exit.tokens")
    if not mass or not tokens:
        return None
    return 100.0 * min(mass) / tokens
