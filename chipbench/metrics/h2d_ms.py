"""Host milliseconds per round of the measured window in the program's
``fl.h2d`` span, the call that starts a block's host-to-device copy
(the copy and the runtime's layout transpose run on after it returns),
from the trainer's span totals taken before and after the untraced
window."""

SPAN = "fl.h2d"


def read(record):
    return record["window"]["spans"]["span_ms"].get(SPAN)
