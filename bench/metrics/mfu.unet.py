"""Model step, U-Net cells: image rows handed back in the window outside
its profiled part, times the operations one row needs
(`bench.shapes_graph`: 12.10 GFLOP for pix2pix-unet256), over that time,
the chips and the chip's matmul peak (bf16).

The profiled part is left out: with the profiler on, the runtime logs
every piece of the host-side relayout of this cell's 12.6 MB transfers,
which slows that part of the window several times over."""
from bench import shapes_graph


def read(run):
    peak = run.shapes.peaks(run.device_kind)
    t = run.trace
    t0 = run.t_end - run.seconds
    rows = sum(r.rows for r in run.records
               if r.done is not None and t0 <= r.done <= run.t_end
               and not t.t_a <= r.done <= t.t_b)
    seconds = run.seconds - t.window_s
    if not rows or seconds <= 0.0:
        return None
    flops = rows * shapes_graph.flops_per_row(run.cfg)
    return 100.0 * flops / (seconds * run.chips
                            * peak["matmul_flops_per_s"])
