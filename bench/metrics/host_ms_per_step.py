"""Host time per scheduler step: the harness's wall span around each
``ContinuousScheduler.step()`` less the device busy time inside it, in ms,
averaged over the traced steps."""


def read(run):
    t = run.trace
    if t is None or not t.step_spans:
        return None
    host = [(e - s) - b for (s, e), b in zip(t.step_spans, t.step_busy_ns)]
    return sum(host) / len(host) / 1e6
