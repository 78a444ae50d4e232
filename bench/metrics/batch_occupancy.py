"""batch_occupancy (scheduler): mean active rows per paged decode step
in the window. Every row of one decode step gets its token at the same
clock reading, so the window's decode tokens (each request's tokens
after its first, which prefill samples) grouped by their time are its
steps."""


def read(run):
    steps = {}
    for r in run.recs:
        for t in r["tokens"][1:]:
            if run.in_window(t):
                steps[t] = steps.get(t, 0) + 1
    return sum(steps.values()) / len(steps) if steps else None
