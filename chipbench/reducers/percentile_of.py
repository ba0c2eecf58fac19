"""A percentile (nearest rank) of a list a driver kept, one entry per
request or per step."""

from ..drivers.serve import percentile


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    values = counters.get(args["counter"])
    return percentile(values, args["q"]) if values else None
