"""Device milliseconds per traced round of the server step: the self time
of the operations under the program's ``fl.server_step`` scope (the
server optimizer and the parameter update), from the profiler trace with
the executed program's scope map (``chipbench/scopes.py``)."""

from chipbench.scopes import scope_ms

SCOPES = ("fl.server_step",)


def read(record):
    return scope_ms(record, SCOPES)
