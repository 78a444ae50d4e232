"""decode_turnaround_ms (scheduler): mean host time per decode step
during which the next step is not yet enqueued: from the end of step
n's ``sched.decode_sync`` to the end of step n+1's
``sched.decode_dispatch``, over consecutive steps whose spans lie wholly
inside the window. Read from the program spans of the trace (the
reduced trace's ``program`` key, ``progspans.with_program``); a trace
without them reads nothing."""

from progspans import decode_turnarounds


def read(run):
    if run.trace is None:
        return None
    turns = decode_turnarounds(run.trace)
    return sum(b - a for a, b in turns) / len(turns) / 1e6 if turns \
        else None
