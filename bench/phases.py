"""The engine's bucket calls split into their phases, from the program's
spans.

With its tracer on, the engine runs each bucket call as a ``dispatch
b<bucket>`` span holding four phase spans that name it as ``parent``:
``dispatch.upload`` (host side of the host-to-device copy),
``dispatch.call`` (the jitted call until it returns: the enqueue),
``dispatch.wait`` (until the device is done) and ``dispatch.copy_back``
(the device-to-host copy).  A program without them yields nothing here.
"""
from __future__ import annotations

import re
from typing import Dict, List

CALL = re.compile(r"dispatch b\d+")
PHASES = ("dispatch.upload", "dispatch.call", "dispatch.wait",
          "dispatch.copy_back")
LAUNCH = ("dispatch.upload", "dispatch.call")


def phase_seconds(spans) -> Dict[int, Dict[str, float]]:
    """For each bucket call among ``spans`` (by its span id), the seconds
    of each of its phases that are among them too."""
    calls: Dict[int, Dict[str, float]] = {
        a["id"]: {} for n, _, _, _, a in spans
        if CALL.fullmatch(n) and "id" in a}
    for n, _, s, e, a in spans:
        if n in PHASES and a.get("parent") in calls:
            calls[a["parent"]][n] = e - s
    return calls


def launch_seconds(spans) -> List[float]:
    """Per bucket call with both, the seconds of its upload and call
    phases: the host's time from entering the call until the device holds
    the work."""
    return [sum(p[k] for k in LAUNCH)
            for p in phase_seconds(spans).values()
            if all(k in p for k in LAUNCH)]
