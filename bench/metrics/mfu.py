"""Model step: image rows handed back in the traced window times the
operations one row needs, over the window, the chips and the chip's
matmul peak (bf16: the float32 path takes one bf16 pass per dot)."""


def read(run):
    peak = run.shapes.peaks(run.device_kind)
    t = run.trace
    rows = sum(r.rows for r in run.records
               if r.done is not None and t.t_a <= r.done <= t.t_b)
    if not rows:
        return None
    flops = rows * run.shapes.flops_per_row(run.cfg)
    return 100.0 * flops / (t.window_s * run.chips
                            * peak["matmul_flops_per_s"])
