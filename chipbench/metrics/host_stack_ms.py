"""Host milliseconds per round of the measured window spent gathering and
stacking the clients' batches: the program's ``fl.stack_batches`` span,
from the trainer's span totals taken before and after the untraced
window.  Since the block loop stacks the next block while the device
runs, this time blocks a round only where it outlasts the device's."""

SPAN = "fl.stack_batches"


def read(record):
    return record["window"]["spans"]["span_ms"].get(SPAN)
