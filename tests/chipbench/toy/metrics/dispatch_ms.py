"""Host milliseconds per round of the window in the program's
``fl.dispatch`` span: a metric that reads a span by name."""


def read(record):
    return record["window"]["spans"]["span_ms"].get("fl.dispatch")
