"""Share of the scorer's tile slots that carried a request in the window
(ServingMetrics.tile_filled / tile_slots)."""


def read(run):
    c = run.counters
    if not c.get("tile_slots"):
        return None
    return 100.0 * c["tile_filled"] / c["tile_slots"]
