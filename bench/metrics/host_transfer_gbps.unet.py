"""Engine, U-Net cells: bytes moved between host and device per second
of the serving thread's own time in the traced window's
``dispatch.upload`` and ``dispatch.copy_back`` phases.  The bytes are
what the engine's ``engine.upload_bytes`` and ``engine.copy_back_bytes``
counters add for each bucket call, carried as the ``bytes`` argument of
those spans.

Not a link rate: the upload phase ends once the copy is enqueued, and
the copy back only waits for what is left of the copy that started at
launch.  It is the host's cost a byte, the part of the transfers that
holds the worker.  A program whose spans carry no bytes yields nothing."""
PHASES = ("dispatch.upload", "dispatch.copy_back")


def read(run):
    moved = seconds = 0.0
    for n, _, s, e, a in run.trace.spans:
        if n in PHASES and "bytes" in a:
            moved += a["bytes"]
            seconds += e - s
    return moved / seconds / 1e9 if seconds > 0.0 else None
