"""Frontend: 95th percentile of the program's ``queue_wait`` spans (submit
to the wave that takes the request) in the traced window."""


def read(run):
    return run.trace.span_percentile_ms(r"queue_wait", 95)
