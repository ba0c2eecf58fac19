"""Mean of one field of the traced engine turns, over the turns that made
a decode tick (args: field, scale)."""

from ._steps import traced_steps


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    vals = [s[args["field"]] for s in traced_steps(red, counters)
            if s["decode"]]
    if not vals:
        return None
    return float(args.get("scale", 1.0)) * sum(vals) / len(vals)
