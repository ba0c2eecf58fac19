"""Device idle time that lies under the named program spans' own time
(innermost span at each instant), as a share of the traced window (args:
spans; causal, the triples that place the spans on the device's clock, see
``program_spans``).  None where the program wrote no ``hvd.*`` span."""

from ..program_spans import idle_partition


def reduce(red, counters, cell):
    part = idle_partition(red, cell)
    if part is None:
        return None
    under = sum(part.get(n, 0.0) for n in cell["spec"]["args"]["spans"])
    return 100.0 * under / red["window_s"]
