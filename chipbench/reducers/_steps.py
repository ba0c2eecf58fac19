"""The engine turns that the profile covered, matched to the trace.

The driver keeps one record per engine turn on the host's clock; the
trace holds one ``chipbench.engine.step`` span per turn made while the
profiler was on.  The first such span is the first turn that began after
``start_trace`` returned, so the n spans are the n records from there on.
"""


def traced_steps(red, counters):
    steps, span = counters.get("steps"), counters.get("profile_span")
    if not steps or not span:
        return []
    n = sum(1 for name, _, _ in red["spans"] if name == "engine.step")
    after = [s for s in steps if s["t0"] >= span[0]]
    return after[:n]
