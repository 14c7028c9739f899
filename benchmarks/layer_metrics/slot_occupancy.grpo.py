"""Share of the rollout engine's decode slots that produced a token."""

from harness import readers


def read(run):
    return readers.slot_occupancy(run, run["driver"].n_slots)
