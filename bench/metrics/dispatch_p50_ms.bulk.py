"""Engine, in the bulk cells: median of the program's ``dispatch
b<bucket>`` spans (upload, call and blocking copy back of one bucket
call) in the traced window."""


def read(run):
    return run.trace.span_percentile_ms(r"dispatch b\d+", 50)
