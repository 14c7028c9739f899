"""How long a freed decode slot waits for its next occupant's first token
(ms): from the end of a ``request`` event to the end of the
``engine.prefill.wait`` of the next ``engine.admit`` that names its slot,
mean over the refills inside the traced window: detection at the drain,
the admission's host work, the prefill's dispatch (blocked or not) and its
run. ``slot_occupancy.gen`` only counts that time; this reads it."""

from harness import spans


def read(run):
    return spans.slot_refill_ms(run)
