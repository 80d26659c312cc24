"""Finding a Pallas kernel's calls in a reduced trace, and its roofline
share from the bytes its instructions move.

On the TPU the trace names each device op by its HLO instruction text
(``%luq_quantize.1 = f32[2048,256]{...} custom-call(f32[2048,256] ...),
custom_call_target="tpu_custom_call", ...``): the instruction's name is
the jitted wrapper that issued the kernel, and its shapes give the bytes.
"""
from __future__ import annotations

from typing import Callable, Optional

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(event, kernel: str) -> bool:
    """A Pallas kernel call whose instruction name holds ``kernel``."""
    name = event.name.split("=", 1)[0]
    return KERNEL_TARGET in event.name and kernel in name


def roofline_share(trace, kernel: str, bytes_per_s: float,
                   instruction_bytes: Callable[[str], int]
                   ) -> Optional[float]:
    """Percent of the kernel's device time that the bytes its calls move
    need at ``bytes_per_s``; None when the stretch ran no such kernel."""
    events = trace.ops_in_window(lambda e: is_kernel(e, kernel))
    if not events:
        return None
    moved = sum(instruction_bytes(e.name) for e in events)
    busy = sum(e.dur for e in events) / 1e9
    if moved == 0 or busy <= 0:
        return None
    return 100.0 * moved / bytes_per_s / busy


def program_time_s(trace, name: str) -> float:
    """Device seconds of the executed programs whose name holds ``name``."""
    return trace.module_time_s(lambda e: name in e.name)
