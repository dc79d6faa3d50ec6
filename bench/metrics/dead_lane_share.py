"""Share of slot-rounds in the window that ran a dead slot (the engine's
occupancy counters): such slots still evaluate the backbone."""


def read(run):
    if not run["slot_rounds"]:
        return None
    return 100.0 * (1.0 - run["live_rounds"] / run["slot_rounds"])
