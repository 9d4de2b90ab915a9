"""Host milliseconds to enqueue one step: the mean length of the
benchmark's span around ``Trainer.update``, less what that call spends
in the train-metric drain (``metric_drain``, nested in it: a host fetch
of the previous step's outputs, which waits for the device and so lasts
about a device step whatever the enqueue costs)."""


def read(view):
    calls = [(t0, t1) for name, t0, t1 in view["spans"] if name == "update"]
    if not calls:
        return None
    drains = [(t0, t1) for name, t0, t1 in view["spans"]
              if name == "metric_drain"]
    spent = sum(t1 - t0 for t0, t1 in calls) - sum(
        t1 - t0 for t0, t1 in drains
        if any(u0 <= t0 and t1 <= u1 for u0, u1 in calls))
    return 1e3 * spent / len(calls)
