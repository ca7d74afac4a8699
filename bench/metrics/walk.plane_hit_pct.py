"""Share of the window's fused walks whose plane operand was already on
the device: session counters ``walk_plane_hits`` over ``walk_plane_hits +
walk_plane_misses``.  None on a program without the plane store."""


def read(record):
    c = record["counters"].get("session", {})
    hits, misses = c.get("walk_plane_hits"), c.get("walk_plane_misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
