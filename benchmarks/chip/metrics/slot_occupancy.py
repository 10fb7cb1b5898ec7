"""Mean share of the batcher's slots that hold a request in a decode tick
(the batcher's ``mean_occupancy`` count over the window), in %."""


def read(run):
    w = run.window
    if not w["batcher_ticks"]:
        return None
    return 100.0 * w["occupancy_sum"] / w["batcher_ticks"] / run.num_slots
