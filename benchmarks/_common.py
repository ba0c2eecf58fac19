"""Shared benchmark plumbing: device fencing and result persistence."""

from __future__ import annotations

import json
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MEASURED_PATH = os.path.join(_REPO, "benchmarks", "measured.jsonl")


def fence(tree) -> None:
    """Device->host readback of one element, so that timing waits for
    the computation and not for its enqueue."""
    import jax

    leaf = tree if not isinstance(tree, (tuple, list, dict)) \
        else jax.tree.leaves(tree)[0]
    float(leaf.ravel()[0])


def persist(record: dict) -> None:
    """Append a measurement record to the committed evidence file."""
    with open(MEASURED_PATH, "a") as f:
        f.write(json.dumps(record) + "\n")
