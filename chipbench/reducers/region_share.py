"""Device seconds of the ops that lie in the named regions of their
program (``op_regions``: the program's ``hvd.*`` regions, read through the
trace's own copy of the compiled module), as a share of the device seconds
of all ops that hold no other ops.  Args: ``regions``, names that match
themselves and what is dotted under them, anywhere in an op's path
(``hvd.block.mlp`` takes the expert layer's ``hvd.moe.*`` inside it too);
``[]`` means the ops with no region; ``phases`` (optional) of ``forward``,
``recompute``, ``backward``; ``programs`` (optional) substrings of the
program's name: numerator and denominator are then of the ops inside those
programs' runs.  None where no op of the trace has a region (a program
from before the regions) or the programs ran nothing."""

from ..op_regions import placed, share


def reduce(red, counters, cell):
    rows = placed(red, cell)
    if rows is None:
        return None
    args = cell["spec"]["args"]
    return share(rows, args["regions"], args.get("phases"),
                 args.get("programs"))
