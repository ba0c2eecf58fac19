"""A counter the harness or a driver kept, as it stands."""


def reduce(red, counters, cell):
    value = counters.get(cell["spec"]["args"]["counter"])
    return None if value is None else float(value)
