"""Plan cache: programs traced while the window ran, the change of the
service's ``plan_traces`` counter between its opening and its close.
Everything the traffic uses is warmed in set-up, so it should read 0."""


def read(run):
    return run.counter("plan_traces", "open", "close")
