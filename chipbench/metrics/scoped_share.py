"""Share of the device's operation time in operations that carry a program
scope (``gnn.*``, ``graph.*``, ``optimizer``) or belong to a jitted program
helper named by its module, in %; the rest is ``(no scope)`` in the note
``trace: device seconds by scope``.  Serves ``scoped_share.<cell kind>``."""
from chipbench import program_trace as P


def read(run):
    pt = P.program(run, __file__)
    if pt is None or not pt.has_scopes():
        return None
    return 100.0 * pt.scoped_s() / sum(pt.device_s.values())
