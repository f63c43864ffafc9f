"""How uneven the routing was: the largest number of tokens one held expert
got in one (layer, step), over the mean load of the (layer, step, held
expert)s that got any, from the program's own counters.  1 is even."""


def read(trace, counters, cell):
    hit = counters.get("moe_expert_steps_hit", 0)
    held = counters.get("moe_assignments_held", 0)
    if not hit or not held:
        return None
    return counters["moe_tokens_per_expert_max"] / (held / hit)
