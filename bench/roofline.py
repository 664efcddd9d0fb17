"""Bytes the kernels' algorithms must move, from their shapes.

One water level (``_waterlevel_kernel``) over ``M`` servers reads each
server's busy time (int32), μ (int32) and eligibility (one byte) and
writes its allocation (int32): 13 bytes a server, over the ``M`` real
servers and not the padded lanes.  It does a few integer operations a
byte, so bytes bound it.
"""

WF_BYTES_PER_SERVER = 4 + 4 + 1 + 4


def wf_level_bytes(m: int) -> int:
    return WF_BYTES_PER_SERVER * int(m)
