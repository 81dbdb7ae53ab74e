"""Share of its roofline that the mpblock kernel (``mp_block_pallas``,
``kernels/mpblock``) reaches: the least time the chip could take for
the window's useful work (``bench/lib/work.py``: unique window pairs x
``2 s`` FLOP against the bf16 peak, and the series in and the profile
out against the HBM peak; compute bounds it by far) over the summed
device time of the kernel's operations in the trace, over every chip.

The work is counted from the problem, so a kernel that sweeps less of
the padded square reads higher, and the share cannot pass 100% unless
the kernel time leaves out part of the work.  The denominator is the
published bf16 peak, since the MXU rate of f32 at ``Precision.HIGHEST``
is not published.  A kernel that replaces the contraction (a
per-diagonal recurrence, say) is another kernel with its own metric.
"""
import re

LAYER = "kernels"
UNIT = "%"
MOVES = "search_s"

#: the kernel as the device trace names it today: a ``tpu_custom_call``
#: whose result is the (row min f32, argmin s32) pair of (blocks, block,
#: 1) arrays; the other Pallas kernels return one array
KERNEL = re.compile(r"^%\S+ = \(f32\[\d+,\d+,1\]\S*, s32\[\d+,\d+,1\]\S*\) "
                    r"custom-call\(.*custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    from bench.lib.work import roofline_s
    c = ctx.counters
    if ctx.trace is None or ctx.peaks is None or not c.get("useful_flop"):
        return None
    busy = ctx.trace.total_op_s(lambda name: bool(KERNEL.search(name)))
    if busy <= 0:
        return None
    return 100.0 * roofline_s(c["useful_flop"], c["useful_bytes"],
                              ctx.peaks) / busy
