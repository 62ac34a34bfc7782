"""Host time a call exposes: its span less the device-busy time inside it,
in milliseconds, over all the calls of the traced window. Call boundaries
are the harness's ``bench.call`` spans, on the trace's clock."""


def read(run):
    if run.trace is None or not run.trace.call_s:
        return None
    exposed = sum(c - b for c, b in zip(run.trace.call_s, run.trace.call_busy_s))
    return exposed / len(run.trace.call_s) * 1e3
