"""Seconds from process start to the window's opening (host clock):
imports, device init, data, bind, prepare, compile or cache load, warm-up."""


def read(run):
    return run.window[0] - run.started
