"""Frontend, in the bulk cells: share of the program's ``wave_dispatch``
spans in the traced window whose wave was launched while another wave
was in flight (the span's ``overlapped`` arg).  A program whose waves
carry no such arg yields nothing."""


def read(run):
    seen = [a["overlapped"] for n, _, _, _, a in run.trace.spans
            if n == "wave_dispatch" and "overlapped" in a]
    return 100.0 * sum(map(bool, seen)) / len(seen) if seen else None
