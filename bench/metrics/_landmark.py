"""The landmark-attention launches of a trace, found by their launch record
(``"kernel": "landmark_attention"``, ``repro.kernels.landmark_attention.
kernel.launch_record``), read from the op's HLO text as ``_named`` reads the
sweep's.

The record is a frontend attribute, and XLA copies it onto the ops it
derives from the launch (the get-tuple-elements of its results and what
they fuse into), so more than one op of a step can carry it.  Launches are
counted by the Pallas custom call alone; their time is that of every op
carrying the record: the launch and the work XLA attributes to it.  A
program without such records yields nothing, and the readers then return
None."""
from __future__ import annotations

from bench.metrics._common import PALLAS
from bench.metrics._named import launch_record


def launches(red):
    """(launches, seconds summed over the devices) of the trace's landmark
    launches, or None."""
    count = secs = 0
    for name, (c, s) in red.ops.items():
        details = red.details.get(name, "")
        rec = launch_record(name) or launch_record(details)
        if rec is None or rec.get("kernel") != "landmark_attention":
            continue
        secs += s
        if PALLAS in name or PALLAS in details:
            count += c
    return (int(count), secs) if count else None
