"""Image rows handed back inside the window, over the window's length."""


def read(run):
    rows = sum(r.rows for r in run.records
               if r.done is not None and r.done <= run.t_end)
    return rows / run.seconds
