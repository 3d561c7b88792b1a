"""setup: how often the program traced the function it traced most often
(the compile listener's count of ``compile/trace`` events per ``fun_name``
under an open host span, ``hostlog.most_traced``; the function's name is on
the log line ``bench: setup spans:``). A kernel body traced nine times cost PR
36 its set-up bound. Nothing where the program keeps no such count."""

import hostlog


def read(run):
    found = hostlog.most_traced(run)
    return found and found[1]
