"""Generated tokens that reached the host inside the window, per second:
each decode tick's tokens counted when the host receives them, plus each
request's first token when its admission wave samples it."""


def read(run):
    decoded = sum(h[1] for h in run.harvests if run.in_window(h[0]))
    firsts = sum(1 for r in run.records.values()
                 if run.in_window(r.first_token_s))
    return (decoded + firsts) / run.window_s()
