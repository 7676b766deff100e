"""(marker x trait) tests whose results reached the writer in the window,
over the window's seconds (host clock).  On four chips: all devices."""


def read(run):
    return run.tests / run.window_s if run.window_s > 0 and run.tests else None
