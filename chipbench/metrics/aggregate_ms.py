"""Device milliseconds per traced round of the aggregation: the self time
of the operations under the program's ``fl.aggregate`` scope and, inside
it, ``fl.flatten`` (the ravel and unravel of the update stack), from the
profiler trace with the executed program's scope map
(``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms

SCOPES = ("fl.aggregate", "fl.flatten")


def read(record):
    return scope_ms(record, SCOPES)
