"""Share of the traced window in which a chip's TensorCore runs the
ring's collective: the union of the chip's ``collective-permute``
operations (start and done, or the op itself) on its ``XLA Ops`` line,
over the window, averaged over the chips.

The ring plan (``core/distributed.py`` ``_ring_mp_shard``) sends the
candidate set to the next chip with ``lax.ppermute`` once a hop.  The
compiler starts each send before the hop's ``mp_block`` and waits for
it after, so what a chip spends on the ``XLA Ops`` line is the
collective's exposed time: issuing it, and waiting in the done for a
transfer the kernel did not hide.  The ``Async XLA Ops`` line is left
out: there each send spans its whole hop, in flight behind the kernel,
and the profiler writes that line for the first chip only, so a mean
over the chips would read a quarter of one chip's window.  None where
the trace holds no such operation, as on one chip.
"""
import re

from bench.lib.trace import busy_ns

LAYER = "ring"
UNIT = "%"
MOVES = "search_s"

#: "%name = <result> <opcode>(...)": the opcode of a full HLO text
_OPCODE = re.compile(r"^%[\w.\-]+ = .*?[})] ([a-z][\w\-]*)\(")
PERMUTE = "collective-permute"


def is_permute(name: str) -> bool:
    """An operation of the permute, by its instruction name or its
    opcode (never an operation that only reads a permute's result)."""
    m = _OPCODE.match(name)
    return (name.lstrip("%").startswith(PERMUTE)
            or bool(m and m.group(1).startswith(PERMUTE)))


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    per_chip = {dev: [e for e in evs if is_permute(e[0])]
                for dev, evs in tr.devices.items()}
    if not any(per_chip.values()):
        return None
    held = sum(busy_ns(evs) for evs in per_chip.values()) / len(per_chip)
    return 100.0 * held * 1e-9 / tr.window_s
