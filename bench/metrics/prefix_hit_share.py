"""prefix_hit_share (paged pool and prefix cache): prompt tokens served
from shared pages (``PageStats.prefix_hit_tokens``, counted at
admission) over the prompt tokens of the requests admitted in the
window, in percent."""


def read(run):
    admitted = sum(r["plen"] for r in run.recs
                   if "t_admit" in r and run.in_window(r["t_admit"]))
    if not admitted or run.pool0 is None or run.pool1 is None:
        return None
    hits = run.pool1.prefix_hit_tokens - run.pool0.prefix_hit_tokens
    return 100.0 * hits / admitted
