"""Engine, in the bulk cells: median of the program's ``dispatch.copy_back``
spans in the traced window, the device-to-host copy of one bucket call's
ready result."""


def read(run):
    return run.trace.span_percentile_ms(r"dispatch\.copy_back", 50)
