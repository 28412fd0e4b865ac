"""Reading the program's own launch records from a trace.

Each pairwise Pallas launch carries a record in its HLO text,
``frontend_attributes={kernel_metadata={"kernel":…,"mxu_flops":…}}``
(``repro.kernels.pairwise.kernel.launch_record``), and a TPU trace names
each operation by that text, so the record is read from the op's name.  A
kernel is found by its ``kernel`` field, not by the shape of its output.

A program without records (one older than them) yields nothing here, and
the readers then return None.
"""
from __future__ import annotations

import json
import re
from typing import List, Optional, Tuple

#: the kernels that run the fused O(n²) sweep
SWEEP_KERNELS = ("pairwise_matmat_multi", "pairwise_matmat_slab")

_RECORD = re.compile(r"kernel_metadata=(\{[^{}]*\})")


def launch_record(text: str) -> Optional[dict]:
    """The launch record in an op's HLO text, or None."""
    m = _RECORD.search(text)
    if m is None:
        return None
    try:
        return json.loads(m.group(1))
    except ValueError:
        return None


def sweep_launches(red) -> List[Tuple[str, int, float, dict]]:
    """(op, events, seconds summed over the devices, record) of the trace's
    sweep launches."""
    out = []
    for name, (count, secs) in red.ops.items():
        rec = launch_record(name) or launch_record(red.details.get(name, ""))
        if rec is not None and rec.get("kernel") in SWEEP_KERNELS:
            out.append((name, int(count), secs, rec))
    return out
