"""Share of the engine's decode slots that produced a token."""

from harness import readers


def read(run):
    return readers.slot_occupancy(run, run["traffic"]["engine"]["n_slots"])
