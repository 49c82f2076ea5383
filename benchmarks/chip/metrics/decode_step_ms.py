"""Device time of one scanned decode step: the decode program's
(``jit_decode``) device seconds in the traced stretch over its
executions and the steps each scans."""


def read(run):
    s = run.step_seconds()
    return None if s is None else 1e3 * s
