"""Assignment rows that fell on this chip's held experts over all the step
programs' assignment rows (tokens x experts a token x expert layers), in %,
over the measured window: the counters ``moe_assignments_held`` and
``moe_assignments_elsewhere`` of ``/metrics.json``, which the step program
counts on the device and returns beside its tokens (``ops/moe.py::held_part``;
the loop recorder keeps the same per turn, ``COL_MOE_HELD``).  With
group-limited routing a token sends this chip none or several of its experts;
two groups held of eight under an even load read 25 %.  Layer: expert layer
(ops/moe.py)."""

from benchmark.lib import window


def read(art, ctx):
    moved = window.counters_moved(art)
    held = moved.get("moe_assignments_held")
    away = moved.get("moe_assignments_elsewhere")
    if held is None or away is None or not held + away:
        return None
    return 100.0 * held / (held + away)
