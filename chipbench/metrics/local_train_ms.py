"""Device milliseconds per traced round of local training: the self time
of the operations the program puts under its ``fl.local_sgd`` scope
(every client's T local steps), from the profiler trace with the
executed program's scope map (``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms

SCOPES = ("fl.local_sgd",)


def read(record):
    return scope_ms(record, SCOPES)
