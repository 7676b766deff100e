"""Seconds of bind and plan: ``Study.from_arrays`` plus ``ScanPlan.prepare``
(covariate basis, panel residualization, step build), from the
benchmark's spans around the two calls."""


def read(run):
    return sum(run.spans.durations("setup.bind")) + sum(run.spans.durations("setup.prepare"))
