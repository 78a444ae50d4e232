"""setup_s: seconds from the process's start to the window's start
(device check, compile cache, weights, engine, warm-up, pre-roll)."""


def read(run):
    return run.setup_s
