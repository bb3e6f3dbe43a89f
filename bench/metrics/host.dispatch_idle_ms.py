"""Device-idle milliseconds per call while the host enqueues the step:
the idle gaps the trace labels with the program's spans ``step/inputs``
(mask, budget and broadcasts), ``worker/launch`` and ``master/dispatch``
(master operands and launch).  None where the profile has no device ops,
or none of the sync drivers' spans ``step/inputs``, ``master/dispatch``
and ``master/fetch`` labels an idle gap."""

SPANS = ("step/inputs", "worker/launch", "master/dispatch")
NEW = ("step/inputs", "master/dispatch", "master/fetch")


def read(r):
    s = r.summary
    idle = dict(s.idle_by_label)
    if s.busy_s <= 0.0 or not any(name in idle for name in NEW):
        return None
    return 1e3 * sum(idle.get(name, 0.0) for name in SPANS) / s.n_calls
