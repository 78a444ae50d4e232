"""kv_page_fill (paged pool and prefix cache): KV positions written over
the positions of the pages the running rows hold, summed over the
window's decode steps, in percent. Counted by the pool at each decode
step (``PageStats.fill_tokens`` / ``fill_capacity``, read at the
window's edges); worst-case reservation at admission and partly written
last pages are the waste it shows. A pool without the counters reads
nothing."""


def read(run):
    if run.pool0 is None or run.pool1 is None:
        return None
    edges = [getattr(p, k, None) for p in (run.pool0, run.pool1)
             for k in ("fill_tokens", "fill_capacity")]
    if None in edges:
        return None
    tok0, cap0, tok1, cap1 = edges
    if cap1 <= cap0:
        return None
    return 100.0 * (tok1 - tok0) / (cap1 - cap0)
