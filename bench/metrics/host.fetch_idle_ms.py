"""Device-idle milliseconds per call while the host waits in the
program's ``master/fetch`` span (the blocking pull of the decode's
scalars), from the idle gaps the trace labels with the innermost open
host span.  None where the profile has no device ops, or none of the sync
drivers' spans ``step/inputs``, ``master/dispatch`` and ``master/fetch``
labels an idle gap."""

SPANS = ("step/inputs", "master/dispatch", "master/fetch")


def read(r):
    s = r.summary
    idle = dict(s.idle_by_label)
    if s.busy_s <= 0.0 or not any(name in idle for name in SPANS):
        return None
    return 1e3 * idle.get("master/fetch", 0.0) / s.n_calls
